"""Share of its roofline that the paged decode kernel reaches: the least
time the chip needs for the pages the pattern makes each active slot
read (the architecture's work counts, ``bench.arch``), over the kernel's
device time in the traced decode steps."""


def read(run, trace):
    if trace is None or run["peaks"] is None or not hasattr(run["work"], "paged_decode"):
        return None
    steps, work, peaks = run["window"].steps, run["work"], run["peaks"]
    need = spent = 0.0
    for i, s in trace.steps.items():
        if s.paged_ms <= 0:
            continue
        flops, byts = work.paged_decode(steps[i].decode_positions)
        need += max(flops / peaks.flops, byts / peaks.hbm_bytes)
        spent += s.paged_ms / 1e3
    return 100.0 * need / spent if spent else None
