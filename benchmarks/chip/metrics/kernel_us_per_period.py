"""Device time of the lane's period kernel per simulated control period
(all draws of a call together), from the profiled calls' trace."""


def read(r):
    if r.profile is None:
        return None
    k = r.profile.kernel_s.get(r.lane, 0.0)
    if k <= 0:
        return None
    return 1e6 * k / (r.profile.calls * r.shape["periods"])
