"""``Qwen3ForCausalLM``, with and without pixelfly: the program's
configuration, the plain float32 reference, and the work it needs.

The reference follows the published Qwen3 decoder: token embedding; per
layer RMSNorm -> q/k/v projections -> per-head RMSNorm of q and k ->
rotary embedding (half split, theta from the config) -> grouped-query
causal attention -> output projection -> residual; RMSNorm -> SwiGLU MLP
-> residual; final RMSNorm; logits against the tied embedding. It runs
one layer at a time over a whole sequence, attention in blocks of
queries, with every product at ``Precision.HIGHEST``, so it fits on the
chip after the program's state is freed.

The pixelfly twin (paper §3.3) replaces each projection by ``W = gamma *
B + (1 - gamma) * U V^T`` where ``B`` is a flat block butterfly, built
here as a dense masked matrix from the pattern ``bench.arch.ops``
derives from the config, and masks attention by blocks: a key block is
visible to a query block when it is among the first ``global_blocks``,
within ``local_blocks`` behind it, or when the two block ids differ in
exactly one bit (a butterfly stride), for strides below the butterfly
grid: the largest power of two not above the slot's block count
(``max_len / block``), as the paged decode schedule reads it.

``Work`` counts operations and bytes from the configuration alone, never
from a kernel's grid, padding or schedule, under the reference's
attention pattern.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch.ops import fp8, mm, pixelfly_cols, rmsnorm, rope, split_sparse_lowrank

__all__ = ["ModelSpec", "model_spec", "program_config", "reference", "work", "Reference", "Work"]

QUERY_BLOCK = 512
HEAD_ROWS = 256
# sequences are padded to a multiple of this, so a few compiled shapes
# serve every request
SEQ_PAD = 2048
PROJECTIONS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/wg", "mlp/wu", "mlp/wd")


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    sparse: bool = False
    density: float = 0.2
    block: int = 128
    lowrank_frac: float = 0.25
    attn_block: int = 128
    local_blocks: int = 2
    global_blocks: int = 1

    def linear_shape(self, name: str) -> tuple[int, int]:
        d, q, kv, f = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim, self.d_ff
        return {
            "attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d), "mlp/wg": (d, f), "mlp/wu": (d, f),
            "mlp/wd": (f, d),
        }[name]

    def pattern(self, name: str) -> tuple[np.ndarray, int, int]:
        """(cols, rank, block) of a pixelfly projection."""
        n_in, n_out = self.linear_shape(name)
        b = self.block
        while b > 8 and (n_in % b or n_out % b):
            b //= 2
        rank, stride = split_sparse_lowrank(n_out, n_in, self.density, b, self.lowrank_frac)
        return pixelfly_cols(n_out, n_in, b, stride), rank, b


def model_spec(conf: dict) -> ModelSpec:
    """The reference's view of a configuration file (published keys)."""
    px = conf.get("pixelfly") or {}
    return ModelSpec(
        layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        sparse=bool(px),
        density=px.get("sparse_density", 0.2),
        block=px.get("sparse_block", 128),
        lowrank_frac=px.get("lowrank_frac", 0.25),
        attn_block=px.get("attn_block", 128),
        local_blocks=px.get("attn_local_blocks", 2),
        global_blocks=px.get("attn_global_blocks", 1),
    )


def program_config(conf: dict, *, smoke: dict | None = None):
    """The program's ``ModelConfig`` for a configuration file, through
    its registry, checked against every published width. ``smoke``
    replaces widths for CPU tests (and the check with it)."""
    from repro.configs import registry

    px = conf.get("pixelfly") or {}
    over = {"norm_eps": float(conf["rms_norm_eps"])}
    if px:
        over.update(
            sparse_density=px["sparse_density"],
            sparse_block=px["sparse_block"],
            lowrank_frac=px["lowrank_frac"],
            attn_local_blocks=px["attn_local_blocks"],
            attn_global_blocks=px["attn_global_blocks"],
            attn_block=px["attn_block"],
        )
    over.update(smoke or {})
    cfg = registry.get(conf["arch"], sparse=bool(px), **over)
    want = {
        "num_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "num_heads": conf["num_attention_heads"],
        "num_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "d_ff": conf["intermediate_size"],
        "vocab_size": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "qk_norm": True,
        "tie_embeddings": conf["tie_word_embeddings"],
        "dtype": conf["torch_dtype"],
        "sparse_attention": bool(px),
        "attn_max_stride": 0,
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"registry config differs from the file: {bad}")
    return cfg


def reference(conf: dict, weights, max_len: int, *, precision: str = "f32") -> "Reference":
    return Reference(model_spec(conf), weights, max_len, precision=precision)


def work(conf: dict, max_len: int) -> "Work":
    return Work(model_spec(conf), max_len)


def stride_grid(spec: ModelSpec, max_len: int) -> int:
    """Butterfly strides of attention lie below this: the largest power
    of two not above a slot's block count."""
    nblk = max(1, max_len // spec.attn_block)
    return 1 << (nblk.bit_length() - 1)


# ----------------------------------------------------------------------
# The reference
# ----------------------------------------------------------------------


class Reference:
    """``logits(tokens, read)``: float32 logits at positions ``read`` of
    the sequence ``tokens`` (prompt then served tokens)."""

    def __init__(self, spec: ModelSpec, weights, max_len: int, *, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        self.spec = spec
        self.weights = weights  # bench.weights.LayerWeights
        self.fp8 = precision == "fp8"
        self.stride_grid = stride_grid(spec, max_len)
        self._layer_fn = jax.jit(self._layer)
        self._head_fn = jax.jit(self._head)
        self._dense_w = jax.jit(self._dense_weights)

    # -- weights ---------------------------------------------------------
    def _dense_weights(self, w: dict) -> dict:
        """Projection matrices (in, out) of one layer, pixelfly made dense."""
        s = self.spec
        out = {}
        for name in PROJECTIONS:
            if not s.sparse:
                out[name] = w[f"{name}/w"]
                continue
            n_in, n_out = s.linear_shape(name)
            cols, rank, b = s.pattern(name)
            blocks = w[f"{name}/blocks"]
            if blocks.shape != (n_out // b, cols.shape[1], b, b):
                raise ValueError(f"{name}: blocks {blocks.shape} do not fit the pattern")
            if w[f"{name}/U"].shape != (n_in, rank):
                raise ValueError(f"{name}: U {w[f'{name}/U'].shape} is not rank {rank}")
            rows = np.broadcast_to(np.arange(n_out // b)[:, None], cols.shape)
            wb = jnp.zeros((n_in // b, n_out // b, b, b), jnp.float32)
            wb = wb.at[cols, rows].add(blocks)
            wb = wb.transpose(0, 2, 1, 3).reshape(n_in, n_out)
            g = w[f"{name}/gamma"]
            low = jnp.matmul(w[f"{name}/U"], w[f"{name}/V"].T, precision=jax.lax.Precision.HIGHEST)
            out[name] = g * wb + (1.0 - g) * low
        for k in ("attn_norm/scale", "mlp_norm/scale", "attn/q_norm", "attn/k_norm"):
            out[k] = w[k]
        return out

    # -- one layer over the whole sequence -----------------------------
    def _visible(self, qpos, kpos):
        s = self.spec
        causal = kpos[None, :] <= qpos[:, None]
        if not s.sparse:
            return causal
        qb = (qpos // s.attn_block)[:, None]
        kb = (kpos // s.attn_block)[None, :]
        diff = qb ^ kb
        stride = (diff > 0) & ((diff & (diff - 1)) == 0) & (diff < self.stride_grid)
        local = (qb >= kb) & (qb - kb < s.local_blocks)
        glob = (kb < s.global_blocks) | (qb < s.global_blocks)
        return causal & (local | glob | stride)

    def _layer(self, x, w, n_real):
        s = self.spec
        f8 = self.fp8
        L = x.shape[0]
        pos = jnp.arange(L)
        h = rmsnorm(x, w["attn_norm/scale"], s.norm_eps)
        q = mm(h, w["attn/wq"], f8).reshape(L, s.heads, s.head_dim)
        k = mm(h, w["attn/wk"], f8).reshape(L, s.kv_heads, s.head_dim)
        v = mm(h, w["attn/wv"], f8).reshape(L, s.kv_heads, s.head_dim)
        q = rope(rmsnorm(q, w["attn/q_norm"], s.norm_eps), pos, s.rope_theta)
        k = rope(rmsnorm(k, w["attn/k_norm"], s.norm_eps), pos, s.rope_theta)
        g = s.heads // s.kv_heads
        kr = jnp.repeat(k, g, axis=1)  # (L, H, D): head h reads kv head h // g
        vr = jnp.repeat(v, g, axis=1)
        kmask = pos < n_real

        def block(qi):
            start = qi * QUERY_BLOCK
            qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, 0)
            qpos = start + jnp.arange(QUERY_BLOCK)
            if f8:
                sc = jnp.einsum("qhd,khd->hqk", fp8(qb, -1), fp8(kr, -1),
                                precision=jax.lax.Precision.HIGHEST)
            else:
                sc = jnp.einsum("qhd,khd->hqk", qb, kr, precision=jax.lax.Precision.HIGHEST)
            sc = sc * s.head_dim ** -0.5
            ok = self._visible(qpos, pos) & kmask[None, :]
            sc = jnp.where(ok[None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            vv = fp8(vr, 0) if f8 else vr
            pp = fp8(p, -1) if f8 else p
            return jnp.einsum("hqk,khd->qhd", pp, vv, precision=jax.lax.Precision.HIGHEST)

        o = jax.lax.map(block, jnp.arange(L // QUERY_BLOCK))
        o = o.reshape(L, s.heads * s.head_dim)
        x = x + mm(o, w["attn/wo"], f8)
        h = rmsnorm(x, w["mlp_norm/scale"], s.norm_eps)
        a = jax.nn.silu(mm(h, w["mlp/wg"], f8)) * mm(h, w["mlp/wu"], f8)
        return x + mm(a, w["mlp/wd"], f8)

    def _head(self, x, read, scale, emb):
        h = rmsnorm(x[read], scale, self.spec.norm_eps)
        if self.fp8:
            h, emb = fp8(h, -1), fp8(emb, None)
        return jnp.einsum("qd,vd->qv", h, emb, precision=jax.lax.Precision.HIGHEST)

    def logits(self, tokens: np.ndarray, read: np.ndarray) -> np.ndarray:
        """(len(read), vocab) float32 logits predicting the token after
        each position in ``read`` of ``tokens``."""
        return self.logits_many([tokens], [read])[0]

    def logits_many(self, seqs: list, reads: list) -> list[np.ndarray]:
        """``logits`` for several sequences, layer by layer: each layer's
        weights are made once for all of them."""
        top = self.weights.top()
        xs, ns = [], []
        for tokens in seqs:
            n = int(tokens.size)
            L = -(-n // SEQ_PAD) * SEQ_PAD
            padded = np.zeros((L,), np.int32)
            padded[:n] = tokens
            xs.append(top["embed/tok"][jnp.asarray(padded)])
            ns.append(n)
        for li in range(self.spec.layers):
            w = self._dense_w(self.weights.layer(li))
            xs = [self._layer_fn(x, w, jnp.int32(n)) for x, n in zip(xs, ns)]
        emb = top["embed/tok"][: self.spec.vocab]
        out = []
        for x, read in zip(xs, reads):
            read = np.asarray(read, np.int32)
            rows_out = []
            for i in range(0, read.size, HEAD_ROWS):
                rows = np.zeros((HEAD_ROWS,), np.int32)
                chunk = read[i : i + HEAD_ROWS]
                rows[: chunk.size] = chunk
                got = self._head_fn(x, jnp.asarray(rows), top["final_norm/scale"], emb)
                rows_out.append(np.asarray(got)[: chunk.size])
            out.append(np.concatenate(rows_out))
        return out


# ----------------------------------------------------------------------
# Work
# ----------------------------------------------------------------------


class Work:
    """Operations and bytes under the reference's attention pattern: a
    query at position ``p`` sees keys ``0..p`` (dense twin), or the keys
    of the visible blocks up to ``p`` (pixelfly twin)."""

    def __init__(self, spec: ModelSpec, max_len: int, itemsize: int = 2):
        self.spec = spec
        self.itemsize = itemsize
        self.stride_grid = stride_grid(spec, max_len)
        self._vis: dict[int, int] = {}
        # name -> (nonzero block weights, low-rank weights, n_in, n_out)
        self.proj = {}
        for name in PROJECTIONS:
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
