"""The Pallas kernel lane: TPU kernels for the repro's compute hot-spots.

Modules
  attention.py  flash prefill (custom_vjp recompute backward), serving
                chunk attention at explicit positions, and the FUSED paged
                decode — the block-table gather runs inside the kernel's
                streaming-softmax KV loop via a scalar-prefetched table, so
                decode reads only the live pool blocks per row instead of
                materialising the gathered context.
  psgn.py       per-sample gradient squared norms for dense layers: direct
                and gram factorisations, plus the fused multi-layer variant
                that stacks same-shape layers into one launch with the
                cross-layer sum accumulated in VMEM.
  quant.py      fused rowwise int8 quantisation for cross-pod grad
                compression.
  gmm.py        grouped matrix products for the dropless expert layer:
                the megablox gmm/tgmm kernels under a custom VJP on TPU,
                ``jax.lax.ragged_dot`` elsewhere.
  ops.py        jit wrappers + dispatch: ``choose_method`` picks the psgn
                factorisation by FLOP count, ``persample_sq_norm_tree``
                groups same-shape layers into the fused kernel.
  (package)     ``default_interpret``, the one platform switch, and
                ``resolve_interpret``, through which every kernel
                resolves ``interpret=None``.
  ref.py        pure-jnp oracles — the property tests in
                tests/test_kernels.py validate every kernel against these
                in interpret mode; TPU is the execution target.

Dispatch into the lane
  Attention: ``cfg.attn_impl = "pallas"`` routes models/transformer.py's
  train forward, prefill, chunked paged prefill, and paged decode through
  attention.py (``models/attention.resolve_impl``); "auto" keeps the XLA
  dense/flash fork at ``configs/base.FLASH_THRESHOLD``.
  Diversity: the exact tier's ``psn_impl = "kernel"`` (train/step.py)
  replaces vmap-of-grad per-sample norms with one probe-gradient pass
  through ``ops.persample_sq_norm_tree``; the gram tier always lands here.
"""

import jax


def default_interpret() -> bool:
    """The one platform switch of the lane: True (interpret mode)
    everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel's ``interpret=`` argument: None defers to
    :func:`default_interpret`, an explicit bool forces that mode."""
    return default_interpret() if interpret is None else bool(interpret)


# submodules import the two functions above from this package
from repro.kernels import attention, gmm, ops, psgn, quant, ref  # noqa: E402

__all__ = ["attention", "default_interpret", "gmm", "ops", "psgn", "quant", "ref",
           "resolve_interpret"]
