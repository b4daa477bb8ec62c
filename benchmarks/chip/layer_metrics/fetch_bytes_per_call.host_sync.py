"""Bytes the host fetches from the device per ``solve`` call, over the
window's calls, from the solver's ``SolveStats.host_fetch_bytes`` (every
``device_get`` of the call, its bytes taken from the fetched arrays'
shapes): the per-chunk scalars and the end-of-call fetch of the whole
worker state."""


def read(ctx, win, device):
    fetched = [getattr(r.stats, "host_fetch_bytes", 0) for _, _, r in win.calls]
    if not win.calls or not sum(fetched):
        return None
    return sum(fetched) / len(win.calls)
