"""Host time per call outside the engine launches: the call's wall time
minus its RunTrace ``chunk`` spans (launch to results on the host), mean
over the window's calls.  Host prep, segment prep and splices."""


def read(r):
    spans = [c for c in r.calls if c["launch_s"] is not None]
    if not spans:
        return None
    return 1e3 * sum(c["wall_s"] - c["launch_s"] for c in spans) / len(spans)
