"""Faults planted underneath the harness, in the system under test, to show
that ``correct`` comes out false for each fault a cell can have.

    unchanged   every train step returns its parameters unchanged
    half_batch  the train step leaves out the second half of its
                microbatches and takes the mean over the rest
    token       the serving sampler alters every 17th token it produces
"""

from __future__ import annotations


def plant(name: str) -> None:
    import jax.numpy as jnp

    if name == "unchanged":
        import repro.train.step as step_lib

        step_lib.apply_updates = lambda params, updates: params
    elif name == "half_batch":
        import repro.train.step as step_lib

        to_micro = step_lib._to_micro

        def half(x, num_micro, dp_size):
            mb = to_micro(x, num_micro, dp_size)
            keep = mb[: max(num_micro // 2, 1)]
            return jnp.concatenate([keep] * (num_micro // len(keep)))

        step_lib._to_micro = half
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

        eng_lib.ServeEngine._sampler_fn = altered
    else:
        raise ValueError(f"unknown fault {name!r}")
