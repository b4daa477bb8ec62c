"""Nodes expanded per superstep over the window's calls, from the solver's
``SolveStats`` counters: how well the center keeps the workers' lanes busy.
A count, exact from run to run."""


def read(ctx, win, device):
    nodes = sum(r.nodes_expanded for _, _, r in win.calls)
    rounds = sum(r.rounds for _, _, r in win.calls)
    return nodes / rounds if rounds else None
