"""The whole step's share of the chip's peak: model FLOPs of the tokens
the window's steps processed (the architecture's work counts, ``bench.arch``:
the matmul weights each token touches, the logits rows, the attention the
pattern lets it see) over the summed wall time of those steps times chips
times peak. Waits for arrivals are left out."""


def read(run, trace):
    win, work, peaks = run["window"], run["work"], run["peaks"]
    if peaks is None or not (hasattr(work, "prefill_flops") and hasattr(work, "decode_flops")):
        return None
    flops = wall = 0.0
    for s in win.steps:
        if s.end > win.close:
            continue
        flops += sum(work.prefill_flops(n) for n in s.prefills)
        flops += sum(work.decode_flops(p) for p in s.decode_positions)
        wall += s.end - s.start
    return 100.0 * flops / (wall * run["chips"] * peaks.flops) if wall else None
