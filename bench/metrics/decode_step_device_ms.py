"""Device time of the decode program per decode step, from the trace."""


def read(run, trace):
    if trace is None:
        return None
    ms = [s.decode_ms for s in trace.steps.values() if s.decode_ms > 0]
    return sum(ms) / len(ms) if ms else None
