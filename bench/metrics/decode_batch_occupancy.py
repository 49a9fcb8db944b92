"""Share of the decode batch in use: tokens the engine's decode steps
emitted over decode steps times slots, from its own counters
(``ServeStats``) over the window."""


def read(run, trace):
    c = run["counters"]
    if not c["decode_steps"]:
        return None
    return 100.0 * c["decode_tokens"] / (c["decode_steps"] * c["max_slots"])
