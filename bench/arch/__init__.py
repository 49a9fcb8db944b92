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

Shared float32 and fp8 building blocks are in ``bench.arch.ops``.
"""
