"""Speculative greedy decoding: draft k tokens, verify in ONE forward.

Sequential decode steps are latency-bound on the device->host round
trip and under-utilise the MXU (batch-1, length-1 matmuls).  Drafting
``k`` candidate tokens and verifying them in a single cached forward of
segment length ``k+1`` turns k sequential steps into one wide step —
output is EXACTLY vanilla greedy (every emitted token is the target
model's argmax; drafts only decide how many argmaxes one forward can
confirm).  The reference has no generation stack at all; this is the
TPU-first latency lever for the generation family.

Two draft sources, both pluggable:

* ``ngram`` (default) — prompt-lookup drafting: propose the tokens that
  followed the most recent occurrence of the current suffix in the
  context.  No second model, no extra memory; shines on inputs whose
  continuations repeat context (summarisation, code edits, RAG).
* ``model`` — a smaller TransformerLM checkpoint decodes k greedy
  tokens as the draft.  Its cache uses the same explicit-length paged
  layout, so rejection rollback is just "set length back".

Cache discipline (the part flax's mutable-cache Generator cannot do):
the verify forward writes K/V for ALL k+1 segment positions, but only
``accepted+1`` become visible — the stream length advances by exactly
that, and rejected slots are overwritten by the next round.  Explicit
lengths make speculative rollback free.

Compiled-program budget: one prefill per prompt bucket + ONE verify
program (fixed k+1 segment) — rounds never re-trace.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from seldon_core_tpu.models.generate import _buckets_for
from seldon_core_tpu.models.paged import get_paged_lm_class, write_kv
from seldon_core_tpu.runtime import knobs as _knobs
from seldon_core_tpu.runtime.component import MicroserviceError, TPUComponent

logger = logging.getLogger(__name__)


def ngram_draft(context: np.ndarray, k: int, ngram: int = 2) -> np.ndarray:
    """Prompt-lookup draft: find the most recent earlier occurrence of
    the trailing ``ngram`` tokens and propose what followed it.

    Returns up to ``k`` proposed tokens (possibly 0 — no match)."""
    n = len(context)
    for width in range(min(ngram, n - 1), 0, -1):
        suffix = context[n - width:]
        # scan right-to-left for the latest match before the suffix itself
        for start in range(n - width - 1, -1, -1):
            if np.array_equal(context[start : start + width], suffix):
                follow = context[start + width : start + width + k]
                if len(follow):
                    return np.asarray(follow, np.int32)
    return np.zeros((0,), np.int32)


class _PagedState:
    """Single-stream paged cache with an identity block table."""

    def __init__(self, module, params, *, max_len: int, page_size: int, dtype,
                 mesh=None, model_axis: str = "model", data_axis: str = "data",
                 min_weight_size: int = 16_384, quantize: str = "",
                 seq_shard: bool = True):
        import jax.numpy as jnp

        from seldon_core_tpu.ops.surgery import validate_quantize_mode

        self.quantize = validate_quantize_mode(quantize)
        self.dtype = dtype
        self.quantize_manifest: list = []
        if quantize == "int8":
            from seldon_core_tpu.ops.surgery import quantize_params

            params, self.quantize_manifest = quantize_params(params)
        self.module = module
        self.max_len = max_len
        self.page_size = page_size
        num_pages = max_len // page_size + 1  # + trash page 0
        # 2-D mesh (r19): page dim shards over the data axis, so round
        # the pool up to a dp multiple (extra tail pages are simply
        # never referenced by the identity table)
        if mesh is not None and seq_shard:
            from seldon_core_tpu.parallel.mesh import mesh_shape

            _dp = mesh_shape(mesh).get(data_axis, 1)
            if _dp > 1 and num_pages % _dp:
                num_pages += -num_pages % _dp
        cfg = module
        shape = (cfg.num_layers, num_pages, page_size, cfg.d_model)
        # same tensor-parallel layout as PagedEngine (shared helper):
        # megatron param specs + pool sharded on heads, created sharded,
        # collectives inserted by XLA; mesh=None -> plain pools
        from seldon_core_tpu.parallel.sharding import shard_decode_state

        self.params, self.pk, self.pv = shard_decode_state(
            params, mesh, pool_shape=shape, dtype=dtype,
            model_axis=model_axis, data_axis=data_axis,
            min_weight_size=min_weight_size,
            num_heads=cfg.num_heads, seq_shard=seq_shard,
        )
        # logical page p lives at pool page p+1 (0 is the trash page)
        self.table = jnp.arange(1, max_len // page_size + 1, dtype=jnp.int32)[None, :]
        self.length = 0  # host-side; rollback = assignment


class SpeculativeGenerator:
    """Greedy generation with draft-and-verify acceleration.

    ``draft="ngram"`` needs nothing extra; ``draft="model"`` takes
    ``draft_params`` (+ ``draft_config`` when its architecture differs
    from the target's).  ``stats`` accumulates acceptance counters so
    serving can export a speculation-efficiency metric.
    """

    def __init__(
        self,
        params,
        *,
        vocab_size: int,
        d_model: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        max_len: int = 2048,
        page_size: int = 64,
        draft: str = "ngram",
        draft_k: int = 4,
        ngram: int = 2,
        draft_params=None,
        draft_config: Optional[Dict[str, int]] = None,
        prompt_buckets: Optional[Sequence[int]] = None,
        dtype: Any = None,
        mesh: Any = None,
        tp: Optional[int] = None,
        dp: Optional[int] = None,
        model_axis: str = "model",
        data_axis: str = "data",
        shard_min_weight_size: int = 16_384,
        quantize: str = "",
        chunk_token_budget: int = 0,
    ):
        import jax
        import jax.numpy as jnp

        # serving-mesh knobs (r11 tp, r19 dp), same precedence as
        # PagedEngine: an explicit mesh wins; otherwise tp=/dp= (or
        # SELDON_TPU_TP/SELDON_TPU_DP) build the 2-D {data, model}
        # serving mesh, shrinking the data axis first with a WARN when
        # the host exposes fewer devices
        if mesh is None:
            from seldon_core_tpu.parallel.mesh import resolve_mesh

            mesh = resolve_mesh(
                tp=tp, dp=dp, model_axis=model_axis, data_axis=data_axis
            )
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} must be a multiple of page_size {page_size}")
        if draft not in ("ngram", "model"):
            raise ValueError(f"draft must be 'ngram' or 'model', got {draft!r}")
        if draft == "model" and draft_params is None:
            raise ValueError("draft='model' needs draft_params")
        self._jax, self._jnp = jax, jnp
        dtype = dtype or jnp.bfloat16
        self.vocab_size = int(vocab_size)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.draft_mode = draft
        self.draft_k = int(draft_k)
        self.ngram = int(ngram)
        self.prompt_buckets = sorted(set(prompt_buckets or _buckets_for(max_len)))
        # chunked prompt prefill (r15, same knob as the paged engine):
        # the prompt forwards in page-aligned chunks of ONE static
        # width instead of one bucket-sized program — bounds the
        # longest device call AND caps prompt-prefill compile diversity
        # at one program per width.  0 = off (the historical
        # bucket-padded prefill, byte-identical programs).
        if not chunk_token_budget:
            chunk_token_budget = int(
                _knobs.raw("SELDON_TPU_CHUNK_TOKEN_BUDGET", "0") or 0
            )
        self.chunk_token_budget = max(0, int(chunk_token_budget))
        if self.chunk_token_budget and self.chunk_token_budget < page_size:
            logger.warning(
                "chunk_token_budget %d is under one page (%d); clamping",
                self.chunk_token_budget, page_size,
            )
            self.chunk_token_budget = page_size
        self.stats = {"rounds": 0, "drafted": 0, "accepted": 0, "tokens": 0}
        # sequence sharding of the single-stream pools over the data
        # axis (r19) — same knob as PagedEngine, read exactly once so
        # both lanes (target + draft) make the same layout decision
        self._seq_shard = _knobs.flag("SELDON_TPU_SEQ_SHARD")

        cls = get_paged_lm_class()
        target_cfg = dict(
            vocab_size=vocab_size, d_model=d_model, num_layers=num_layers,
            num_heads=num_heads, max_len=max_len, dtype=dtype,
            # as in PagedEngine: no pallas kernel under a TP mesh (GSPMD
            # cannot partition the call); the flat pool's ndim no longer
            # says so by itself
            decode_kernel=mesh is None,
        )
        self.target = _PagedState(
            cls(**target_cfg), params, max_len=max_len, page_size=page_size,
            dtype=dtype, mesh=mesh, model_axis=model_axis,
            data_axis=data_axis, seq_shard=self._seq_shard,
            min_weight_size=shard_min_weight_size, quantize=quantize,
        )
        self.quantize_manifest = self.target.quantize_manifest
        self.draft_state: Optional[_PagedState] = None
        if draft == "model":
            cfg = dict(target_cfg)
            cfg.update(draft_config or {})
            cfg["vocab_size"] = vocab_size  # must share the vocabulary
            cfg["max_len"] = max_len
            self.draft_state = _PagedState(
                cls(**cfg), draft_params, max_len=max_len, page_size=page_size,
                dtype=dtype, mesh=mesh, model_axis=model_axis,
                data_axis=data_axis, seq_shard=self._seq_shard,
                min_weight_size=shard_min_weight_size, quantize=quantize,
            )

        self._forward_jit: Dict[Tuple[int, int, bool], Any] = {}

    # ---- compiled pieces --------------------------------------------------

    def _forward(self, state: _PagedState, tokens: np.ndarray, start: int):
        """Run ``tokens`` (1, L) through the cached forward at absolute
        positions start..start+L-1; returns greedy ids (L,) and advances
        nothing (caller owns state.length)."""
        jax, jnp = self._jax, self._jnp
        # start==0 is the prompt prefill: write whole page blocks (one
        # DUS per page) instead of unrolling one DUS per token — the
        # token-wise branch would trace 2L sequential updates for an
        # L-token prompt.  Static per-program flag, so it joins the key.
        from_zero = start == 0
        key = (id(state.module), tokens.shape[1], from_zero)
        if key not in self._forward_jit:

            def run(params, pk, pv, toks, start, table):
                from seldon_core_tpu.ops.surgery import materialize

                params = materialize(params, state.quantize, state.dtype)
                positions = start + jnp.arange(toks.shape[1])[None, :]
                positions = jnp.minimum(positions, state.max_len - 1)
                logits, nk, nv = state.module.apply(
                    {"params": params}, toks, positions, pk, pv,
                    table, jnp.full((1,), start, jnp.int32),
                )
                pk, pv = write_kv(
                    pk, pv, nk, nv, table, jnp.full((1,), start, jnp.int32),
                    jnp.ones_like(toks, bool),
                    page_size=state.page_size, max_len=state.max_len,
                    from_zero=from_zero,
                )
                return jnp.argmax(logits[0], axis=-1), pk, pv

            self._forward_jit[key] = jax.jit(run, donate_argnums=(1, 2))
        greedy, state.pk, state.pv = self._forward_jit[key](
            state.params, state.pk, state.pv, self._jnp.asarray(tokens),
            self._jnp.asarray(start, self._jnp.int32), state.table,
        )
        return np.asarray(greedy)

    def _forward_chunk(self, state: _PagedState, tokens: np.ndarray,
                       start: int):
        """One page-aligned prompt chunk at absolute offset ``start``:
        reads the pool through the full table masked at
        ``lengths=start`` (earlier chunks' KV), writes whole page
        blocks through the table WINDOW at ``start``'s page (page 0 —
        the trash page — pads a window that runs past the table, the
        same redirection the engine's prefill uses).  One compiled
        program per chunk WIDTH, shared by every offset: ``start`` and
        the window are traced."""
        jax, jnp = self._jax, self._jnp
        W = tokens.shape[1]
        wpages = -(-W // self.page_size)
        key = (id(state.module), W, "chunk")
        if key not in self._forward_jit:

            def run(params, pk, pv, toks, start, table, wtable):
                from seldon_core_tpu.ops.surgery import materialize

                params = materialize(params, state.quantize, state.dtype)
                positions = start + jnp.arange(toks.shape[1])[None, :]
                positions = jnp.minimum(positions, state.max_len - 1)
                logits, nk, nv = state.module.apply(
                    {"params": params}, toks, positions, pk, pv,
                    table, jnp.full((1,), start, jnp.int32),
                )
                pk, pv = write_kv(
                    pk, pv, nk, nv, wtable, jnp.zeros((1,), jnp.int32),
                    jnp.ones_like(toks, bool),
                    page_size=state.page_size, max_len=state.max_len,
                    from_zero=True,
                )
                return jnp.argmax(logits[0], axis=-1), pk, pv

            self._forward_jit[key] = jax.jit(run, donate_argnums=(1, 2))
        shift = int(start) // self.page_size
        window = np.asarray(state.table[0, shift : shift + wpages])
        wt = np.zeros((1, wpages), np.int32)
        wt[0, : len(window)] = window
        greedy, state.pk, state.pv = self._forward_jit[key](
            state.params, state.pk, state.pv, jnp.asarray(tokens),
            jnp.asarray(start, jnp.int32), state.table, jnp.asarray(wt),
        )
        return np.asarray(greedy)

    def _prefill_prompt(self, state: _PagedState, prompt: np.ndarray) -> int:
        """Prompt prefill for one state; returns the next greedy token.
        Monolithic bucket-padded forward by default; with
        ``chunk_token_budget`` set, page-aligned chunks of one static
        width (the r15 slice shape) — same KV, same argmax, bounded
        device calls."""
        plen = len(prompt)
        budget = self.chunk_token_budget
        if not budget or plen <= budget:
            bucket = next(b for b in self.prompt_buckets if b >= plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            greedy = self._forward(state, padded, 0)
            state.length = plen
            return int(greedy[plen - 1])
        W = (budget // self.page_size) * self.page_size
        start = 0
        greedy = None
        n = 0
        while start < plen:
            n = min(W, plen - start)
            seg = np.zeros((1, W), np.int32)
            seg[0, :n] = prompt[start : start + n]
            greedy = self._forward_chunk(state, seg, start)
            start += n
            state.length = start
        return int(greedy[n - 1])

    # ---- drafting ---------------------------------------------------------

    def _draft(self, context: np.ndarray, k: int) -> np.ndarray:
        if self.draft_mode == "ngram":
            return ngram_draft(context, k, ngram=self.ngram)
        # draft model: its cache is already valid up to draft_state.length;
        # catch up on the tokens it has not seen, then decode k greedy steps
        ds = self.draft_state
        missing = context[ds.length :]
        out: List[int] = []
        token_seg = np.asarray(missing, np.int32)[None, :]
        while len(out) < k:
            greedy = self._forward(ds, token_seg, ds.length)
            ds.length += token_seg.shape[1]
            nxt = int(greedy[-1])
            out.append(nxt)
            token_seg = np.asarray([[nxt]], np.int32)
        return np.asarray(out, np.int32)

    # ---- the loop ---------------------------------------------------------

    def generate(
        self, prompt: np.ndarray, max_new_tokens: int = 32, eos_id: int = -1
    ) -> np.ndarray:
        """(plen,) int prompt -> (max_new,) greedy ids, eos-padded.

        Exactness invariant: identical to running the plain cached
        greedy decode token by token."""
        jnp = self._jnp
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        max_new_tokens = int(max_new_tokens)
        if plen < 1 or max_new_tokens < 1:
            raise MicroserviceError(
                "need a non-empty prompt and max_new_tokens >= 1",
                status_code=400, reason="BAD_REQUEST",
            )
        # the verify segment may scribble up to draft_k+1 positions past
        # the accepted length; keep every write inside the table
        if plen + max_new_tokens + self.draft_k + 1 > self.max_len:
            raise MicroserviceError(
                f"prompt {plen} + max_new {max_new_tokens} + draft_k "
                f"{self.draft_k} headroom exceeds max_len {self.max_len}",
                status_code=400, reason="SEQUENCE_TOO_LONG",
            )

        # fresh single-stream state per call (stateless serving surface)
        self.target.length = 0
        if self.draft_state is not None:
            self.draft_state.length = 0

        next_token = self._prefill_prompt(self.target, prompt)
        if self.draft_state is not None:
            # prime the draft cache on the same prompt
            self._prefill_prompt(self.draft_state, prompt)

        out: List[int] = [next_token]
        while len(out) < max_new_tokens and next_token != eos_id:
            context = np.concatenate([prompt, np.asarray(out, np.int32)])
            k = min(self.draft_k, max_new_tokens - len(out))
            drafted = self._draft(context, k)[:k]
            # verify segment: [last emitted, d1..dk] padded to draft_k+1
            # (one static program); pads are never accepted
            seg = np.zeros((1, self.draft_k + 1), np.int32)
            seg[0, 0] = next_token
            seg[0, 1 : 1 + len(drafted)] = drafted
            greedy = self._forward(self.target, seg, self.target.length)
            accepted = 0
            while accepted < len(drafted) and drafted[accepted] == greedy[accepted]:
                accepted += 1
            emitted = list(drafted[:accepted]) + [int(greedy[accepted])]
            self.target.length += accepted + 1
            if self.draft_state is not None:
                # accepted tokens match what the draft model generated, so
                # its cache is valid through them; the bonus token is new
                self.draft_state.length = min(
                    self.draft_state.length, self.target.length - 1
                )
            self.stats["rounds"] += 1
            self.stats["drafted"] += len(drafted)
            self.stats["accepted"] += accepted
            for token in emitted:
                out.append(int(token))
                if len(out) >= max_new_tokens or token == eos_id:
                    break
            next_token = out[-1]
        self.stats["tokens"] += min(len(out), max_new_tokens)

        out = out[:max_new_tokens]
        if eos_id in out:
            cut = out.index(eos_id) + 1
            out = out[:cut]
        out = out + [eos_id] * (max_new_tokens - len(out))
        return np.asarray(out, np.int32)


class SpeculativeLM(TPUComponent):
    """Deployable speculative-greedy generation component.

    Parameters mirror GenerativeLM plus ``draft`` ("ngram" | "model"),
    ``draft_k``, ``ngram`` and ``draft_uri``/``draft_config`` for a
    draft-model checkpoint.  ``metrics()`` exports the acceptance rate
    so speculation efficiency lands on the dashboards.
    """

    device_exclusive = True  # TPU-resident weights/KV: one process per chip

    def __init__(
        self,
        vocab_size: int = 32000,
        d_model: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        max_len: int = 2048,
        max_new_tokens: int = 32,
        eos_id: int = -1,
        model_uri: str = "",
        draft: str = "ngram",
        draft_k: int = 4,
        ngram: int = 2,
        draft_uri: str = "",
        draft_config: Optional[Dict[str, int]] = None,
        page_size: int = 64,
        seed: int = 0,
        mesh_axes: Optional[Dict[str, int]] = None,
        tp: int = 0,
        dp: int = 0,
        quantize: str = "",
        chunk_token_budget: int = 0,
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        self.config = dict(
            vocab_size=int(vocab_size), d_model=int(d_model),
            num_layers=int(num_layers), num_heads=int(num_heads),
            max_len=int(max_len),
        )
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = int(eos_id)
        self.model_uri = model_uri
        self.draft = draft
        self.draft_k = int(draft_k)
        self.ngram = int(ngram)
        self.draft_uri = draft_uri
        self.draft_config = dict(draft_config or {})
        self.page_size = int(page_size)
        self.seed = int(seed)
        # same knobs as StreamingLM: {"model": N} -> tensor-parallel
        # decode; tp=N (or SELDON_TPU_TP when 0) is the
        # deployment-facing spelling of mesh_axes={"model": N}, and
        # dp=D (or SELDON_TPU_DP) adds the data axis of the 2-D
        # serving mesh — an explicit mesh_axes wins over both
        self.mesh_axes = dict(mesh_axes) if mesh_axes else None
        self.tp = int(tp)
        self.dp = int(dp)
        from seldon_core_tpu.ops.surgery import validate_quantize_mode

        self.quantize = validate_quantize_mode(quantize)  # fail at construction
        # chunked prompt prefill (r15): 0 defers to the
        # SELDON_TPU_CHUNK_TOKEN_BUDGET knob inside the generator
        self.chunk_token_budget = int(chunk_token_budget)
        self.generator: Optional[SpeculativeGenerator] = None
        import threading

        # one paged pool + host-side lengths per generator: concurrent
        # predicts must serialize or they would interleave scatters into
        # the same donated buffers (use several replicas to parallelise)
        self._gen_lock = threading.Lock()
        self._load_lock = threading.Lock()

    def load(self) -> None:
        # idempotent AND locked: executor load() + concurrent lazy
        # predict loads must not swap the generator (and its paged
        # pool) mid-use
        with self._load_lock:
            if self.generator is not None:
                return
            self._load_locked()

    def _load_locked(self) -> None:
        import jax.numpy as jnp

        from seldon_core_tpu.models.generate import load_lm_params

        params = load_lm_params(self.model_uri, self.config, self.seed)
        draft_params = None
        if self.draft == "model":
            cfg = dict(self.config)
            cfg.update(self.draft_config)
            cfg["vocab_size"] = self.config["vocab_size"]
            cfg["max_len"] = self.config["max_len"]
            draft_params = load_lm_params(self.draft_uri, cfg, self.seed + 1)
        from seldon_core_tpu.parallel.mesh import mesh_from_axes

        mesh = mesh_from_axes(self.mesh_axes)
        # tp passed THROUGH so the generator resolves the knob exactly
        # once: an explicit tp=1 here must force single-chip even with
        # SELDON_TPU_TP exported (mesh_axes still wins)
        self.generator = SpeculativeGenerator(
            params, dtype=jnp.bfloat16, page_size=self.page_size,
            draft=self.draft, draft_k=self.draft_k, ngram=self.ngram,
            draft_params=draft_params, draft_config=self.draft_config,
            mesh=mesh, tp=self.tp or None, dp=self.dp or None,
            quantize=self.quantize,
            chunk_token_budget=self.chunk_token_budget,
            **self.config,
        )

    def predict(self, X, names, meta=None):
        with self._gen_lock:
            if self.generator is None:
                self.load()
            meta = meta or {}
            tags = meta.get("tags", {})
            max_new = int(tags.get("max_new_tokens", self.max_new_tokens))
            X = np.atleast_2d(np.asarray(X, np.int32))
            return np.stack([
                self.generator.generate(row, max_new_tokens=max_new, eos_id=self.eos_id)
                for row in X
            ])

    def metrics(self):
        s = self.generator.stats if self.generator else {}
        drafted = max(1, s.get("drafted", 0))
        # GAUGEs: metrics() is collected after EVERY request, so a
        # cumulative value exported as COUNTER would be inc()'d
        # repeatedly and grow quadratically (jaxserver does the same
        # for its batch counters)
        return [
            {"type": "GAUGE", "key": "speculative_acceptance_rate",
             "value": s.get("accepted", 0) / drafted},
            {"type": "GAUGE", "key": "speculative_rounds",
             "value": s.get("rounds", 0)},
        ]

    def class_names(self):
        return []
