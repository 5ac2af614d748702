"""One package per model family, found by the name a configuration file
gives under ``entry`` (``harness/spec.py: load_family``). What is one
trunk's lives here and nowhere else in the benchmark: the binding to the
program's model, the seeded weights, the plain reference and the work count.
The harness, the readers and the serve loop ask a family for:

``serve_model(cfg, seed)``
    the program's model as it is served, the seeded weights in it;
``pool_args(model, serving)``
    the keyword arguments of ``Router.add_model`` that say what the pool has
    to hold, from the configuration's ``serving`` block (``num_pages`` among
    them);
``make_weights(cfg, seed)``
    the reference's own weights from the seed, whole (a family whose
    reference runs a layer at a time makes them so in its own modules);
``logits_at(weights, ids, rows, cfg, precision)``
    the reference's float32 logits at the (row, position) pairs ``rows`` of
    a causal forward over ``ids``; ``precision`` is ``"f32"`` (float32 at
    ``HIGHEST``: the reference) or one of ``CONTROLS``;
``CONTROLS``
    the precisions below the one the configuration states, nearest first;
``work``
    the work count (``harness/work.py``): ``prompt_flops(cfg, new,
    cached)``, ``decode_flops(cfg, context)``, ``train_flops_per_token(cfg,
    seq_len)`` and ``KERNELS``, a ``Kernel`` for each kernel that has a
    ``<kernel>_roofline`` metric.

A family that cannot train leaves ``train_flops_per_token`` out, and one
whose kernels have no roofline metric yet keeps ``KERNELS`` empty: a reader
that finds nothing to ask returns nothing. A new family is a new directory
here, its entry modules under ``benchmarks/entries/`` and a configuration
file that names it; no file that is there changes."""
