"""The trace reduction, on a profiler trace recorded on one TPU v5e
(``bench/trace/testdata``): seven steps of the pixelfly twin's engine at
full width, 32 slots decoding after 1,024-token prompts, a 300-page
pool, each step in a ``step`` span."""

import gzip
import shutil

import pytest

from bench.peaks import PEAKS, peaks_for
from bench.trace import reduce as R

DATA = R.__file__.rsplit("/", 1)[0] + "/testdata/pixelfly_decode_steps.xplane.pb.gz"


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "slice.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return R.reduce_file(str(path))


def test_busy_within_window(red):
    assert 0 < red.busy_s <= red.window_s
    assert red.ops and all(op.dur >= 0 for op in red.ops)


def test_decode_program_and_kernels_found(red):
    kinds = {op.kernel for op in red.ops}
    assert {"paged_decode", "bsr_matmul"} <= kinds
    decode_steps = [s for s in red.steps.values() if s.decode_ms > 0]
    assert decode_steps
    for s in decode_steps:
        # the kernels run inside the program that contains them
        assert 0 < s.paged_ms < s.decode_ms
        assert 0 < s.bsr_decode_ms < s.decode_ms
        assert s.paged_ms + s.bsr_decode_ms < s.decode_ms
    for op in red.ops:
        if op.kernel == "paged_decode":
            assert op.name == "decode/paged_decode"


def test_breakdown(red):
    top = red.top_ops(10)
    assert 0 < len(top) <= 10
    assert all(isinstance(n, str) and t > 0 for n, t in top)
    assert sum(t for _, t in top) <= red.busy_s + 1e-9
    assert not any(n.endswith("/while") for n, _ in top)
    gaps = red.idle_gaps(10)
    assert len(gaps) <= 10
    assert all(n in R.HOST_SPANS + ("harness",) and t > 0 for n, t in gaps)
    assert [t for _, t in gaps] == sorted((t for _, t in gaps), reverse=True)


def test_union_and_kind():
    assert R._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert R._kind("%copy.175 = bf16[28] copy(x)") == "copy"
    assert R._kind("%paged_decode_attention_pallas.11 = bf16[32]") == "paged_decode_attention_pallas"
    assert R._kind("%copy-done.2 = bf16[4]") == "copy-done"


def test_peaks_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert p.flops == 197e12 and p.hbm_bytes == 819e9 and "v5e" in p.source
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    assert set(PEAKS) == {"TPU v5 lite"}
