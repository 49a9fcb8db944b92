"""Device time of the prefill programs per 1,000 prompt tokens, over the
traced steps that admitted requests."""


def read(run, trace):
    if trace is None:
        return None
    steps = run["window"].steps
    ms = toks = 0
    for i, s in trace.steps.items():
        if s.prefill_ms > 0 and steps[i].prefill_tokens:
            ms += s.prefill_ms
            toks += steps[i].prefill_tokens
    return 1e3 * ms / toks if toks else None
