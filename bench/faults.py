"""Faults planted in the program's timed path, underneath the harness, to
show that ``correct`` catches each fault a cell can have.  Used by
``calibrate.py`` (readings on the chip at the cell's own size) and by
``tests/test_cells.py`` (at a tiny size on the CPU).  The benchmark's own
runs never plant anything."""

from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "loss_altered", "logit_altered",
          "count_altered", "token_altered")


def _wrap_step(change):
    """Patch ``repro.train.make_train_step`` so that each step it builds is
    ``change(step)``."""
    import repro.train as train
    make = train.make_train_step

    def patched(cfg, opt_cfg, **kw):
        return change(make(cfg, opt_cfg, **kw))
    train.make_train_step = patched
    return lambda: setattr(train, "make_train_step", make)


@contextlib.contextmanager
def planted(fault: str):
    if fault == "state_unchanged":            # a step that changes nothing
        def change(step):
            def frozen(p, o, b):
                return p, o, step(p, o, b)[2]
            return frozen
        undo = _wrap_step(change)
    elif fault == "half_batch":               # mean over half the rows
        def change(step):
            def half(p, o, b):
                n = b["inputs"].shape[0] // 2
                return step(p, o, {k: v[:n] for k, v in b.items()})
            return half
        undo = _wrap_step(change)
    elif fault == "loss_altered":             # the loss, where it is made
        def change(step):
            def altered(p, o, b):
                p, o, m = step(p, o, b)
                return p, o, dict(m, loss=m["loss"] * 1.01)
            return altered
        undo = _wrap_step(change)
    elif fault == "logit_altered":            # one logit of the forward
        import repro.models as models
        fwd = models.forward

        def forward(*a, **kw):
            logits, cache = fwd(*a, **kw)
            return logits.at[0, 0, 0].add(64.0), cache
        models.forward = forward
        undo = lambda: setattr(models, "forward", fwd)       # noqa: E731
    elif fault == "count_altered":            # one per-object count
        from repro.kernels import ops
        hist, agg = ops.object_histogram, ops.trace_aggregate

        def histogram(*a, **kw):
            counts = hist(*a, **kw).copy()
            counts[0] += 1
            return counts

        def aggregate(*a, **kw):
            counts, hot = agg(*a, **kw)
            counts = counts.copy()
            counts[0] += 1
            return counts, hot
        ops.object_histogram, ops.trace_aggregate = histogram, aggregate

        def undo():
            ops.object_histogram, ops.trace_aggregate = hist, agg
    elif fault == "token_altered":            # each request's second token
        from repro.serve.engine import ServeEngine
        sample = ServeEngine._sample_one

        def sample_one(self, req, logits_row, position=None):
            tok = sample(self, req, logits_row, position)
            pos = len(req.tokens) if position is None else position
            return (tok + 1) % len(logits_row) if pos == 1 else tok
        ServeEngine._sample_one = sample_one

        def undo():
            ServeEngine._sample_one = sample
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    try:
        yield
    finally:
        undo()
