"""Share of its roofline that the block-sparse matmul kernel reaches
inside the decode program: the least time for the nonzero blocks and
the active slots' rows (the architecture's work counts, ``bench.arch``),
over the kernel's device time in the traced decode steps."""


def read(run, trace):
    if trace is None or run["peaks"] is None or not hasattr(run["work"], "bsr_matmul"):
        return None
    steps, work, peaks = run["window"].steps, run["work"], run["peaks"]
    need = spent = 0.0
    for i, s in trace.steps.items():
        if s.bsr_decode_ms <= 0:
            continue
        flops, byts = work.bsr_matmul(len(steps[i].decode_positions))
        need += max(flops / peaks.flops, byts / peaks.hbm_bytes)
        spent += s.bsr_decode_ms / 1e3
    return 100.0 * need / spent if spent else None
