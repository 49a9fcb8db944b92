"""Operations and bytes that the served models' algorithm needs, from the
configuration alone: never from a kernel's grid, padding or schedule.

The attention pattern is the reference's (``bench.reference``): a query
at position ``p`` sees keys ``0..p`` (dense twin), or the keys of the
visible blocks up to ``p`` (pixelfly twin).
"""

from __future__ import annotations

from bench.reference import ModelSpec

__all__ = ["Work"]


class Work:
    def __init__(self, spec: ModelSpec, max_len: int, itemsize: int = 2):
        self.spec = spec
        self.itemsize = itemsize
        nblk = max(1, max_len // spec.attn_block)
        self.stride_grid = 1 << (nblk.bit_length() - 1)
        self._vis: dict[int, int] = {}
        # name -> (nonzero block weights, low-rank weights, n_in, n_out)
        self.proj = {}
        for name in ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/wg", "mlp/wu", "mlp/wd"):
            n_in, n_out = spec.linear_shape(name)
            if spec.sparse:
                cols, rank, b = spec.pattern(name)
                nnz = cols.size * b * b
                self.proj[name] = (nnz, rank * (n_in + n_out), n_in, n_out)
            else:
                self.proj[name] = (n_in * n_out, 0, n_in, n_out)

    # -- attention pattern -------------------------------------------
    def visible_blocks(self, qb: int) -> int:
        """Blocks a query in block ``qb`` reads, its own included."""
        if qb not in self._vis:
            self._vis[qb] = self._count_blocks(qb)
        return self._vis[qb]

    def _count_blocks(self, qb: int) -> int:
        s = self.spec
        if not s.sparse:
            return qb + 1
        seen = {qb}
        seen.update(range(min(s.global_blocks, qb)))
        if qb < s.global_blocks:
            seen.update(range(qb))
        seen.update(k for k in range(qb - s.local_blocks + 1, qb) if k >= 0)
        t = 1
        while t < self.stride_grid:
            if qb ^ t < qb:
                seen.add(qb ^ t)
            t <<= 1
        return len(seen)

    def visible_keys(self, p: int) -> int:
        b = self.spec.attn_block
        return (self.visible_blocks(p // b) - 1) * b + p % b + 1

    def prompt_visible_keys(self, n: int) -> int:
        """Sum of visible keys over query positions ``0..n-1``."""
        b = self.spec.attn_block
        total = 0
        for qb in range(-(-n // b)):
            rows = min(b, n - qb * b)
            full = (self.visible_blocks(qb) - 1) * b
            total += rows * full + rows * (rows + 1) // 2
        return total

    # -- per token ---------------------------------------------------
    @property
    def matmul_params(self) -> int:
        """Weights one token multiplies through the layers (pixelfly:
        nonzero blocks and low-rank factors)."""
        per_layer = sum(nnz + low for nnz, low, _, _ in self.proj.values())
        return per_layer * self.spec.layers

    @property
    def head_flops(self) -> int:
        return 2 * self.spec.d_model * self.spec.vocab

    def attn_flops(self, keys: int) -> int:
        """QK and PV over ``keys`` key positions, every layer."""
        s = self.spec
        return 4 * s.heads * s.head_dim * keys * s.layers

    def prefill_flops(self, n: int) -> int:
        """A prompt of ``n`` tokens, logits for its last."""
        return 2 * self.matmul_params * n + self.attn_flops(self.prompt_visible_keys(n)) + self.head_flops

    def decode_flops(self, p: int) -> int:
        """One decode token at position ``p``, with its logits."""
        return 2 * self.matmul_params + self.attn_flops(self.visible_keys(p)) + self.head_flops

    # -- kernels -----------------------------------------------------
    def paged_decode(self, positions) -> tuple[float, float]:
        """(FLOPs, bytes) of one decode step's paged attention, all
        layers, for slots at ``positions``: whole pages of K and V of
        every visible block, the query and the output."""
        s = self.spec
        page_bytes = 2 * s.kv_heads * s.attn_block * s.head_dim * self.itemsize
        qo = 2 * s.heads * s.head_dim * self.itemsize
        flops = byts = 0
        for p in positions:
            flops += 4 * s.heads * s.head_dim * self.visible_keys(p)
            byts += self.visible_blocks(p // s.attn_block) * page_bytes + qo
        return flops * s.layers, byts * s.layers

    def bsr_matmul(self, rows: int) -> tuple[float, float]:
        """(FLOPs, bytes) of the block-sparse products of one step over
        ``rows`` token rows, all projections and layers: the nonzero
        blocks once, the rows in and out."""
        flops = byts = 0
        for nnz, _, n_in, n_out in self.proj.values():
            flops += 2 * rows * nnz
            byts += (nnz + rows * (n_in + n_out)) * self.itemsize
        return flops * self.spec.layers, byts * self.spec.layers
