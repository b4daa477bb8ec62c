"""Degree-2 candidates that rule 3 of the vertex-cover reduction examined
per lane sweep, over the window's calls, from the solver's ``SolveStats``
counters: ``reduce_rule3_probes`` (in each sweep where rules 1 and 2 do not
fire, the candidates in index order up to and including the first whose
two neighbours are adjacent, or all of them) over ``reduce_lane_sweeps``.
Exact from run to run.  A program that tests every vertex's row in every
sweep reads n here; one without the counter reads nothing."""


def read(ctx, win, device):
    stats = [r.stats for _, _, r in win.calls]
    probes = sum(getattr(s, "reduce_rule3_probes", 0) for s in stats)
    sweeps = sum(getattr(s, "reduce_lane_sweeps", 0) for s in stats)
    if not sweeps or not all(hasattr(s, "reduce_rule3_probes") for s in stats):
        return None
    return probes / sweeps
