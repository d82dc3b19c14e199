"""Engine-launch time per call: the sum of its RunTrace ``chunk`` spans,
each from the launch until the chunk's results are on the host, mean
over the window's calls."""


def read(r):
    spans = [c["launch_s"] for c in r.calls if c["launch_s"] is not None]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
