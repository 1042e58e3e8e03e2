"""Which decode lane a replica can run: the backend, the mesh, the
compute type and the widths decide it, and nothing above this module
is asked (the blocks, the engine and the tests all ask here)."""

from __future__ import annotations

from typing import Optional

from seldon_core_tpu.runtime import knobs as _knobs


def paged_kernel_mode() -> str:
    """The ``SELDON_TPU_PAGED_KERNEL`` env value ("0" | "1" | "auto" |
    "force") — the ONE place its vocabulary lives.  The LM's kernel
    gate and the engine's chunk-impl auto-select both read through
    here, so a new mode string cannot leave them silently disagreeing.
    Since the r18 default flip the unset value is "auto": the kernel
    lane is the production decode path on single-chip TPU backends, and
    "0" restores the XLA gather lane byte-for-byte."""
    return _knobs.raw("SELDON_TPU_PAGED_KERNEL", "auto")


def paged_kernel_explicit(mode: Optional[str] = None) -> bool:
    """True when the operator EXPLICITLY opted in ("1" | "force") —
    the modes whose ineligibility deserves a WARN.  "auto" degrading to
    the gather lane is a default resolving, not a broken request, so it
    stays silent (the ``kernel_active`` gauge reports which lane won)."""
    return (mode if mode is not None else paged_kernel_mode()) in ("1", "force")


def paged_kernel_requested(mode: Optional[str] = None) -> bool:
    """Whether this process WANTS the pallas decode kernel: an explicit
    "1"/"force", or the "auto" default resolving on a TPU backend
    (off-TPU "auto" means the gather lane, so CPU/GPU processes keep
    the historical flat pool and programs byte-for-byte)."""
    mode = mode if mode is not None else paged_kernel_mode()
    if mode in ("1", "force"):
        return True
    if mode == "auto":
        import jax

        return jax.default_backend() == "tpu"
    return False


def paged_kernel_static_eligible(mode: str, mesh_absent: bool, dtype,
                                 heads: int, head_dim: int,
                                 latent: bool = False) -> bool:
    """THE pallas decode-kernel gate, shared by the LM's trace-time
    choice of lane and the engine's chunk-impl auto-select so the two
    cannot drift: requested by env (explicitly or via the "auto"
    default on TPU), no TP mesh (GSPMD can't partition the pallas
    call), a bf16 or f32 pool (f32 is the exactness lane the
    kernel-parity tests pin), a TPU backend unless forced (interpret
    mode), and — where Mosaic compiles it — a 128-aligned ``heads *
    head_dim`` with ``heads`` the K/V heads (a grouped-query spec's
    ``kv_heads``: the pool's row, not q's): the kernel DMAs ``(page_size,
    heads * head_dim)`` page slices out of HBM and Mosaic wants that
    minor dim in whole lane tiles (the interpreter takes any width).  A replica it turns down
    serves the ring chunk and the XLA gather.  The block adds only its
    trace-local term (a decode step) on top.  ``latent``: the pool's
    element is one latent row and the latent kernel's
    (``ops/kernels.latent_attention_decode``), which cuts the row at its
    128-aligned rank itself, so the width rule is not asked."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops import kernels

    return (
        paged_kernel_requested(mode)
        and mesh_absent
        and dtype in (jnp.bfloat16, jnp.float32)
        and (mode == "force" or jax.default_backend() == "tpu")
        and (latent or (heads * head_dim) % 128 == 0
             or kernels.interpret_mode())
    )


def paged_kv_dtype_mode() -> str:
    """The ``SELDON_TPU_KV_DTYPE`` env value ("bf16" | "int8") — int8
    stores KV pages quantised with one f32 scale per page per k/v in a
    sibling ``(layers, num_pages)`` scale table (r18).  Anything other
    than "int8" means the pool stores the engine dtype natively."""
    return _knobs.raw("SELDON_TPU_KV_DTYPE", "bf16") or "bf16"
