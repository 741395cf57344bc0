"""What the benchmark knows of one kind of model, found by the configuration
file's ``model_type``: ``bench/families/<model_type>.py``
(``bench.cells.family``). A configuration of a new kind is added with a new
module here; nothing else names a model type, and an unknown one is an
error, never a fallback.

A family module holds three parts, each written from the published
description of the model and free of the program's arithmetic:

The model as the program runs it
    ``model(name, cfg)``: the program's model object (its ``ArchConfig``
    and class) for the configuration ``cfg`` as run; it raises where the
    configuration asks for what the program cannot run.

The plain reference (``bench/reference.py`` judges served tokens with it)
    ``REFERENCE_KEYS``: the configuration keys ``forward`` reads.
    ``forward(params, tokens, cfg, lowp=False)``: (T,) token ids -> (T,
    vocab) float32 logits from the benchmark's FP4 weights; ``lowp=True`` is
    the control, with every matmul input rounded to float8
    (``reference.round_lowp``).

The counts (``bench/counts.py`` dispatches to them)
    ``layer_linears(cfg)``, ``linears(cfg)``, ``kv_bytes_per_token(cfg)``,
    ``attention_decode_work(cfg, positions)``,
    ``decode_step_work(cfg, active, positions, run=None)``,
    ``prefill_work(cfg, prompt, chunk, run=None)`` and
    ``cascade_matmul_work(cfg, active, run=None)``. ``run`` is the run's
    ``bench.record.Record``, for work that depends on what the program did
    (which experts a step routed to, read from its counters with
    ``bench.phases.window_counters(run)``); a count asked without a run
    has only the shapes to go by.

Optionally, weight rules
    ``fill(names, sds, key, k_in)``: a leaf the generic rules of
    ``bench/weights.py`` do not cover, drawn from ``key``, or None where
    this family has no rule for it either (the leaf then raises).
"""
