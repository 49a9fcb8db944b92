"""Architecture modules: what the harness needs to know of one published
architecture, one file each.

A configuration file's ``architectures[0]`` selects its module by name:
``bench/arch/<architectures[0] in lower case>.py`` (``Qwen3ForCausalLM``
-> ``bench/arch/qwen3forcausallm.py``). A module provides:

``program_config(conf, *, smoke=None)``
    The program's ``ModelConfig`` for the configuration file ``conf``,
    checked against every published width it states; raises where the
    program would run something else. ``smoke`` replaces fields for the
    CPU tests.
``reference(conf, weights, max_len, *, precision="f32")``
    The plain float32 reference, independent of the program, as an
    object with ``logits(tokens, read)`` and ``logits_many(seqs,
    reads)``; ``weights`` is a ``bench.weights.LayerWeights``.
    ``precision="fp8"`` is the control, one precision below.
``work(conf, max_len)``
    The operations and bytes the architecture needs, from the
    configuration alone, as an object with any of ``prefill_flops(n)``,
    ``decode_flops(p)``, ``paged_decode(positions)`` and
    ``bsr_matmul(rows)``. A per-layer metric whose count the object
    lacks reads nothing.

and may provide:

``cache_shapes(cfg, n_pages, page, slots)``
    The tree of ``ShapeDtypeStruct`` that the program's serving cache
    holds for ``cfg`` (the program's configuration as the engine runs
    it) with a pool of ``n_pages`` pages of ``page`` tokens and
    ``slots`` sequences: the paged pools and any state kept per slot,
    such as a state-space layer's. ``harness.size_pool`` lowers the
    decode and prefill programs on it, so all of it is counted in what
    each program needs; a page's bytes are its growth from one pool
    size to another, which per-slot state leaves alone. Without it the
    tree is the program's ``init_paged_cache(cfg, n_pages, page)``.

Shared float32 and fp8 building blocks are in ``bench.arch.ops``.
"""
