"""What a decoder block is made of: the one description the paged
block, its decode-chunk twin, the LM around them and the parameter
initialiser all read.

The paged engine's contract with a model is narrow: per layer a cache
element of ``num_heads x head_dim = d_model`` for K and for V, written
after whatever the model does to K (positions, norms), and next-token
logits.  Everything around the cache is the model's: how positions
enter (a learned table added to the embedding | rotary embedding on q
and k before the cache write), which norm (LayerNorm | RMSNorm), a
norm on q and k, and the feed-forward (dense GELU MLP | routed SwiGLU
experts, :mod:`seldon_core_tpu.ops.moe`).  ``GPT2`` and ``OLMOE`` are
the two values served; a new architecture is a new value (and new
branches where the block reads a field it has not met), not a new
block.

``head_dim`` is not a field: the pool's element is ``d_model`` wide and
heads split it evenly, so it is ``d_model // num_heads`` everywhere.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict


@dataclass(frozen=True)
class ModelSpec:
    name: str = "gpt2"
    positions: str = "learned"    # "learned" | "rope"
    norm: str = "layernorm"       # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-6
    qk_norm: bool = False         # RMSNorm over the whole q / k projection
    ffn: str = "gelu"             # "gelu" (dense, 4x) | "moe" (routed SwiGLU)
    num_experts: int = 0
    experts_per_tok: int = 0
    expert_width: int = 0
    rope_theta: float = 10_000.0
    bias: bool = True             # projections and head carry a bias
    # the residual stream between blocks: the compute type (bf16 when
    # serving) | float32.  Every matmul still takes bf16 operands; what
    # float32 saves is one rounding to 8 bits of mantissa per residual
    # add, which is what a routed model's near-ties at the k-th expert
    # are decided by
    residual_f32: bool = False
    # how matrices and embeddings rest on the device: float32, cast to
    # the compute type inside every program (TransformerLM's trees) |
    # in the compute type, made so and never cast (norm scales and a
    # router rest in float32 either way)
    weights_f32: bool = True

    @property
    def routed(self) -> bool:
        return self.ffn == "moe"

    @property
    def transformer_lm(self) -> bool:
        """Whether ``models/transformer.py TransformerLM`` builds this
        block.  It builds one — learned positions, LayerNorm, biased
        dense GELU MLP, float32 at rest — and then its ``init`` makes
        the tree, values included, as every GPT-2 test, checkpoint and
        reference expects; any other block's tree is
        :func:`init_params`'s."""
        return (self.positions, self.norm, self.qk_norm, self.ffn, self.bias,
                self.weights_f32) == ("learned", "layernorm", False, "gelu",
                                      True, True)

    @property
    def rope(self) -> bool:
        return self.positions == "rope"


GPT2 = ModelSpec()

# allenai/OLMoE-1B-7B-0125-Instruct config.json: RoPE theta 10,000,
# RMSNorm eps 1e-5, 64 experts of width 1024, top-8, gates not
# renormalised; QK-norm is in modeling_olmoe.py, not a config key
OLMOE = ModelSpec(
    name="olmoe", positions="rope", norm="rmsnorm", norm_eps=1e-5,
    qk_norm=True, ffn="moe", num_experts=64, experts_per_tok=8,
    expert_width=1024, rope_theta=10_000.0, bias=False, residual_f32=True,
    weights_f32=False,
)

_ARCHS = {"gpt2": GPT2, "olmoe": OLMOE}
_SIZES = ("num_experts", "experts_per_tok", "expert_width", "rope_theta",
          "norm_eps")


def model_spec(arch: str = "gpt2", **sizes: Any) -> ModelSpec:
    """The spec named ``arch`` with the given sizes in place of the
    published ones (tests serve an 8-expert OLMoE).  A size of 0/None
    keeps the published value; an unknown ``arch`` or a size the arch
    does not have is a ``ValueError``."""
    try:
        spec = _ARCHS[arch or "gpt2"]
    except KeyError:
        raise ValueError(
            f"arch={arch!r}: the paged engine serves {sorted(_ARCHS)}"
        ) from None
    given = {k: v for k, v in sizes.items() if v}
    unknown = sorted(set(given) - set(_SIZES))
    if unknown:
        raise ValueError(f"model_spec: unknown sizes {unknown}")
    if not spec.routed and given.keys() & _SIZES[:3]:  # the expert sizes
        raise ValueError(f"arch={spec.name!r} has no experts to size")
    if given:
        spec = replace(spec, **{
            k: (float(v) if k in ("rope_theta", "norm_eps") else int(v))
            for k, v in given.items()
        })
    if spec.routed and not 0 < spec.experts_per_tok <= spec.num_experts:
        raise ValueError(
            f"experts_per_tok {spec.experts_per_tok} of {spec.num_experts} "
            "experts")
    return spec


def rope(x, positions, theta: float):
    """Rotary embedding, rotate-half form: ``x`` ``(B, L, heads, hd)``,
    ``positions`` ``(B, L)`` absolute token indices.  Computed in f32."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, L, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# the parameter initialiser: the tree is the module's own
# ---------------------------------------------------------------------------

def init_params(spec: ModelSpec, config: Dict[str, int], seed: int,
                dtype=None):
    """Seeded random weights of the block ``spec`` describes, whatever
    it is made of: the tree (names, shapes, at-rest types) is what the
    paged LM itself declares — ``jax.eval_shape`` of its ``init``, so a
    new field of the spec needs no second description here — and every
    leaf is made on the default device **in the type it rests in**
    (``spec.weights_f32`` false: matrices and embeddings in the compute
    type ``dtype``, bf16 when serving; norm scales and the router f32).
    No f32 copy of a matrix is ever resident (at OLMoE's size one would
    be 14 GB).

    Every leaf is uniform — bits, a scale and a shift, so the CPU (the
    benchmark's reference) and the chip (the server) make the same tree
    from the same seed — over a range its name picks: a norm's
    ``scale`` in [0.5, 1.5) and a ``bias`` within ±0.1, so that one left
    out shows; an ``embedding`` ±sqrt(3) (unit variance); any other
    leaf is a matrix, ±sqrt(3 / fan_in) with the fan-in its
    second-to-last dim, experts' included (unit-variance outputs).  A
    leaf's stream is keyed by its path, not its place in the tree."""
    import zlib

    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    from seldon_core_tpu.models.paged import get_paged_lm_class

    dtype = dtype or jnp.bfloat16
    # the gather lane's module: same tree as every other lane's, and it
    # takes one layer's pool at a time, so any small pool will do
    lm = get_paged_lm_class()(dtype=dtype, spec=spec, decode_kernel=False,
                              **config)
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    pool = jax.ShapeDtypeStruct(
        (config["num_layers"], 2, 8, config["d_model"]), dtype)
    declared = jax.eval_shape(
        lm.init, jax.random.key(0), i32((1, 8)), i32((1, 8)), pool, pool,
        i32((1, 1)), i32((1,)))["params"]

    # one compiled program per distinct (shape, range, type): the
    # layers' leaves share them
    @partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def uniform(key, shape, lo, hi, leaf_dtype):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(leaf_dtype)

    root = jax.random.key(int(seed) % (1 << 63))
    tree = {}
    for path, leaf in flatten_dict(declared, sep="/").items():
        name = path.rsplit("/", 1)[-1]
        if name == "scale":
            lo, hi = 0.5, 1.5
        else:
            hi = (0.1 if name == "bias"
                  else (3.0 if name == "embedding" else 3.0 / leaf.shape[-2]) ** 0.5)
            lo = -hi
        key = jax.random.fold_in(root, zlib.crc32(path.encode()))
        tree[path] = uniform(key, leaf.shape, lo, hi, jnp.dtype(leaf.dtype))
    return unflatten_dict(tree, sep="/")
