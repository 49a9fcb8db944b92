"""How unevenly a router spread the work over its replicas: the tokens
each replica emitted in the window (its engine's own counters), as the
largest less the smallest over their mean. Nothing to read behind one
engine."""


def read(run, trace):
    toks = run["counters"]["replica_tokens"]
    if len(toks) < 2 or not sum(toks):
        return None
    return 100.0 * (max(toks) - min(toks)) / (sum(toks) / len(toks))
