"""What the mixing of a residual of several rows (manifold-constrained
hyper-connections; the program's ``ops/hyper.py``) needs, computed from
shapes, and which of a trace's operations are its: shared by the
``hyper_mix_*`` readers (not a metric itself).

**Bytes and FLOPs**, a position of one mixed sub-layer, float32 rows of
``hc_mult`` = n x ``hidden_size`` = C:

* ``pre``  reads the n rows once: ``4 n C`` bytes (the coefficients and
  the row the sub-layer reads come of that one pass);
* ``post`` reads them again, reads the sub-layer's row and writes n
  rows: ``4 (2 n C + C)`` bytes;
* the projection onto the ``2n + n^2`` coefficients is ``2 n C (2n +
  n^2)`` FLOPs, the two mixes ``2 n C`` and ``2 n C (n + 1)``; ``phi``
  (``4 n C (2n + n^2)`` bytes) is read once a CALL, not a position.

At n = 4, C = 3,584: 186,368 B and 860,160 FLOP a position-sub-layer,
4.6 FLOP/B against the v5e's ridge of 240: the bytes bound it, and the
least time is bytes over the chip's HBM bytes/s.  Work *needed*: one
pass each way, float32 rows, no padding of a call to its tile.

**Which operations are the mixing's** (``trace["ops"]`` is keyed by
opcode plus the first output's type and shape; ``moe_work.py`` says why
a reader has nothing else).  By whole shape: a float32 output of three
or more dims whose last is ``hidden_size`` and whose first is
``hc_mult`` (the rows written: ``hyper_post_mix``, or XLA's fusions of
the same shape on a lane without the kernels) or 1 with a ``pallas_kernel``
opcode (the row read: ``hyper_pre_mix``'s first output ``(1, positions,
C)``).  No other cell has a hidden size of 3,584, no program of this one
a group of 4 prompts (its cap is one prompt a call), and the latent and
prefill kernels' outputs end in 512 and 128.  XLA's scraps around the
kernels (the coefficients' ``(positions, 128)`` pad and slices) have no
shape of their own and are left out: microseconds a call.
"""

from __future__ import annotations

from layer_metrics.pool_move_share_pct import shape_of as dims_of

F32 = 4


def streams(config: dict):
    """``(n, C, mixed sub-layers)`` of a configuration whose residual is
    several rows, or None."""
    model = config.get("model") or {}
    try:
        sizes = (int(model["hc_mult"]), int(model["hidden_size"]),
                 2 * int(model["num_hidden_layers"]))
    except (KeyError, TypeError, ValueError):
        return None
    return sizes if sizes[0] > 1 else None


def position_bytes(config: dict) -> float:
    n, c, _subs = streams(config)
    return float(F32 * c * (n + 2 * n + 1))


def position_flops(config: dict) -> float:
    n, c, _subs = streams(config)
    return 2.0 * n * c * (2 * n + n * n) + 2.0 * n * c + 2.0 * n * c * (n + 1)


def least_seconds(config: dict, positions: float, peaks: dict) -> float:
    """The least time the chip could take to mix ``positions``
    position-sub-layers: the larger of bytes over HBM bytes/s and FLOPs
    over bf16 FLOP/s."""
    return max(positions * position_bytes(config) / peaks["hbm_bytes_per_s"],
               positions * position_flops(config) / peaks["bf16_flops"])


def is_mixing(key: str, config: dict) -> bool:
    n, c, _subs = streams(config)
    dims = dims_of(key)
    if len(dims) < 3 or dims[-1] != c or "_f32_" not in key:
        return False
    return dims[0] == n or (dims[0] == 1 and key.startswith("pallas_kernel"))


def mixing_seconds(trace: dict, config: dict):
    """``(calls, seconds)`` of the traced mixing operations."""
    hits = [v for k, v in trace["ops"].items() if is_mixing(k, config)]
    return sum(v["count"] for v in hits), sum(v["seconds"] for v in hits)
