"""One file per model family, found by the ``family`` key of a configuration
(``harness/manifest.py``).  A family file is everything the harness has to
know about one kind of learner update, and the only place that knows it:

    update_flops(shapes, state_shape, num_actions)
        FLOPs one learner update needs, counted from the configuration's
        ``shapes`` group with the primitives of ``harness/shapes.py``.
        Read by ``layer_metrics/mfu.py`` and by nothing else, so a cell
        that lists no ``mfu`` never calls it.
    seed_chunk(key, n, lrn)
        ``n`` seeded rows made ON THE DEVICE, in the type the ring's
        ``feed_chunk`` takes (traced under ``jax.jit``).
    update_priorities(lrn)
        The program's own priority write-back ``f(ring state, index, |TD|,
        alpha) -> ring state``, with which ``program.fill_ring`` spreads the
        priorities after the fill; None where the ring has none.
    build_step(lrn, steps_per_call=None)
        The learner's step program as ``run_learner`` builds it:
        ``step(train state, ring state, keys, beta) -> (train state, ring
        state, metrics)``.
    agrees(lrn, cfg, reference, seed)
        The comparison that decides ``correct``: a dict with ``ok``.

A later PR adds a family as a new file here (plus its plain reference under
``reference/``); it may import what it shares from a family that is there.
"""
