"""Faults planted underneath the harness, in the system under test, to show
that ``correct`` comes out false for each fault a cell can have.

    unchanged   every train step returns its parameters unchanged
    half_batch  the train step leaves out the second half of its
                microbatches and takes the mean over the rest
    lost_shard  the train step's gradient leaves out the rows the last
                data shard holds (the exchange between chips loses one
                chip's share), still taken as the mean over every row; the
                loss still counts them
    token       the serving sampler alters every 17th token it produces
"""

from __future__ import annotations


def plant(name: str):
    """Plant the fault ``name``; returns a call that takes it out again (the
    engines built after that trace the sound program)."""
    import jax.numpy as jnp

    if name == "unchanged":
        import repro.train.step as step_lib

        return _swap(step_lib, "apply_updates", lambda params, updates: params)
    elif name == "half_batch":
        import repro.train.step as step_lib

        to_micro = step_lib._to_micro

        def half(x, num_micro, dp_size):
            mb = to_micro(x, num_micro, dp_size)
            keep = mb[: max(num_micro // 2, 1)]
            return jnp.concatenate([keep] * (num_micro // len(keep)))

        return _swap(step_lib, "_to_micro", half)
    elif name == "lost_shard":
        import jax

        import repro.models.transformer as tf
        from repro.dist.plan import current_plan

        xent = tf.xent_chunked

        def lost(x, kernel, targets, chunk=512, softcap=None):
            plan = current_plan()
            b = x.shape[0]
            b_lost = b // (plan.dp_size if plan is not None else 1)
            kept = (jnp.arange(b) < b - b_lost)[:, None, None]
            full = xent(x, kernel, targets, chunk, softcap)
            part = xent(jnp.where(kept, x, 0), kernel, targets, chunk, softcap)
            return jax.lax.stop_gradient(full - part) + part

        return _swap(tf, "xent_chunked", lost)
    elif name == "token":
        from repro.serve import engine as eng_lib

        sampler = eng_lib.ServeEngine._sampler_fn

        def altered(self):
            sample = sampler(self)
            vocab = self.cfg.vocab_size

            def fn(logits, rids, pos):
                tok = sample(logits, rids, pos)
                return jnp.where(pos % 17 == 0, (tok + 1) % vocab, tok).astype(tok.dtype)

            return fn

        return _swap(eng_lib.ServeEngine, "_sampler_fn", altered)
    else:
        raise ValueError(f"unknown fault {name!r}")


def _swap(owner, attr: str, value):
    old = getattr(owner, attr)
    setattr(owner, attr, value)
    return lambda: setattr(owner, attr, old)
