"""Autoregressive generation: kv-cache prefill + bucketed decode.

TPU-first shape discipline throughout (the reference has no generation
stack; this extends the serving framework the direction long-context
deployments need):

* **prefill** runs the whole (bucket-padded) prompt through one cached
  forward — one XLA program per prompt bucket;
* **decode** is a single ``lax.scan`` over ``max_new_tokens`` steps of
  a batch-1-token cached forward — one compiled program regardless of
  how many tokens are generated, no Python in the loop;
* EOS handling is mask-based (finished rows keep stepping but their
  outputs freeze), so control flow stays static for the compiler;
* prompt lengths bucket to powers of two: a serving process compiles
  ``len(buckets)`` prefill programs + 1 decode program, then never
  traces again — the same "no request pays a trace" invariant the
  jaxserver bucket ladder enforces.

``GenerativeLM`` wraps this as a deployable component: token ids in,
generated ids out, temperature/top-k sampling, explicit seeding.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from seldon_core_tpu.runtime.component import MicroserviceError, TPUComponent


def _buckets_for(max_len: int) -> List[int]:
    out, b = [], 16
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


def load_lm_params(model_uri: str, config: Dict[str, int], seed: int,
                   spec: Any = None):
    """Shared checkpoint loader for the generation lanes (GenerativeLM /
    StreamingLM / SpeculativeLM): init the tree shape, then overlay a
    flax msgpack checkpoint from the storage downloader when
    ``model_uri`` is set.

    ``spec`` (models/spec.py; None = GPT-2) says what the block is made
    of.  The one block ``TransformerLM`` builds keeps that module's own
    tree, made in float32 (a checkpoint's type; ``Generator`` and the
    speculative lanes serve it so, a ``PagedEngine`` casts it to its
    compute type once, as it takes it: models/spec.py ``rest_tree``);
    any other is ``spec.init_params``: the tree the paged LM declares
    for that spec, each leaf made in the type it rests in (bf16 matrices
    for OLMoE: an f32 tree of its size does not fit the chip)."""
    import jax
    import jax.numpy as jnp

    if spec is None or spec.transformer_lm:
        from seldon_core_tpu.models.transformer import TransformerLM

        module = TransformerLM(dtype=jnp.bfloat16, **config)
        params = module.init(
            jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        from seldon_core_tpu.models.spec import init_params

        params = init_params(spec, config, seed)
    if model_uri:
        from flax import serialization

        from seldon_core_tpu.utils import storage

        path = storage.download(model_uri)
        with open(path, "rb") as f:
            params = serialization.from_bytes(params, f.read())
    return params


class Generator:
    """Compiled generation harness around a TransformerLM checkpoint."""

    def __init__(
        self,
        params,
        *,
        vocab_size: int,
        d_model: int = 256,
        num_layers: int = 4,
        num_heads: int = 8,
        max_len: int = 2048,
        dtype: Any = None,
        prompt_buckets: Optional[Sequence[int]] = None,
        mesh: Any = None,
        tp: Optional[int] = None,
        dp: Optional[int] = None,
        quantize: str = "",
    ):
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.transformer import TransformerLM

        dtype = dtype or jnp.bfloat16
        self.max_len = int(max_len)
        self.vocab_size = int(vocab_size)
        from seldon_core_tpu.ops.surgery import validate_quantize_mode

        self.quantize = validate_quantize_mode(quantize)
        self.quantize_manifest: List[Dict[str, Any]] = []
        if quantize == "int8":
            # weight-only int8 (same surgery as jaxserver): weights rest
            # in HBM at half the bytes and dequantise ONCE per compiled
            # call (measured 1.38x decode rate on TPU; per-step dequant
            # measured 0.48x — see _build_generate)
            from seldon_core_tpu.ops.surgery import quantize_params

            params, self.quantize_manifest = quantize_params(params)
        self._compute_dtype = dtype
        # serving-mesh knobs (r11 tp, r19 dp), same precedence as
        # PagedEngine: an explicit mesh wins; otherwise tp=/dp= (or
        # SELDON_TPU_TP/SELDON_TPU_DP) build the 2-D {data, model}
        # serving mesh (shrinking the data axis first with a WARN on
        # small hosts).  Megatron-sharded params pin the layout —
        # their specs only name the model axis, so weights replicate
        # over data implicitly; the mutable flax cache is created
        # inside the compiled programs, so GSPMD propagates the head
        # sharding through it and inserts the collectives — mesh=None
        # keeps the historical single-chip path byte-identical.
        if mesh is None:
            from seldon_core_tpu.parallel.mesh import resolve_mesh

            mesh = resolve_mesh(tp=tp, dp=dp)
        self._mesh = mesh
        if mesh is not None:
            from seldon_core_tpu.parallel.mesh import mesh_shape
            from seldon_core_tpu.parallel.sharding import shard_params

            self.params = shard_params(params, mesh)
            self.tp_degree = int(mesh_shape(mesh).get("model", 1))
            self.dp_degree = int(mesh_shape(mesh).get("data", 1))
        else:
            # pin on device: surgery/msgpack trees are host numpy, and
            # numpy args to jit re-upload every call
            self.params = jax.device_put(params)
            self.tp_degree = 1
            self.dp_degree = 1
        self.module = TransformerLM(
            vocab_size=vocab_size, d_model=d_model, num_layers=num_layers,
            num_heads=num_heads, max_len=max_len, dtype=dtype, decode=True,
        )
        self.prompt_buckets = sorted(set(prompt_buckets or _buckets_for(max_len)))

        def init_cache(batch: int):
            # shapes only (jax.eval_shape): a real module.init would
            # trace every parameter initializer inside each compiled
            # generate program just to be discarded; the cache starts
            # as plain zeros either way
            shapes = jax.eval_shape(
                lambda: self.module.init(
                    jax.random.key(0), jnp.zeros((batch, 1), jnp.int32),
                    positions=jnp.zeros((1,), jnp.int32),
                )
            )["cache"]
            return jax.tree_util.tree_map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes
            )

        def prefill(params, cache, tokens, true_len):
            """Padded prompt -> (next-token logits at true_len-1, cache).
            Takes already-materialised (fp) params — run() dequantises
            once at program entry."""
            positions = jnp.arange(tokens.shape[1])
            logits, mutated = self.module.apply(
                {"params": params, "cache": cache},
                tokens, positions=positions, mutable=["cache"],
            )
            # the pad region polluted nothing (causal mask), but the
            # running index must reflect the TRUE length so the first
            # decode step lands right after the prompt
            cache = self._set_index(mutated["cache"], true_len)
            last = logits[jnp.arange(logits.shape[0]), true_len - 1]
            return last, cache

        def decode_step(params, cache, token, pos):
            """One cached step: token (B,1), absolute pos (B,) -> logits.
            Callers materialize quantized params ONCE at program entry —
            measured on TPU, per-step dequant does not fuse into the
            matmuls and re-materializes the fp tree every step (0.48x)."""
            logits, mutated = self.module.apply(
                {"params": params, "cache": cache},
                token, positions=pos[:1], mutable=["cache"],
            )
            return logits[:, 0], mutated["cache"]

        self._init_cache = init_cache
        self._prefill = jax.jit(prefill)
        self._decode_step = decode_step  # jitted inside the scan below
        self._generate_jit: Dict[Tuple[int, int, int], Any] = {}
        self._jax, self._jnp = jax, jnp

    def _materialize(self, params):
        """Once-per-program dequant of int8 weights (no-op for fp)."""
        from seldon_core_tpu.ops.surgery import materialize

        return materialize(params, self.quantize, self._compute_dtype)

    @staticmethod
    def _set_index(cache, true_len):
        """Overwrite every layer's cache_index with the true prompt length."""
        import jax

        def fix(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            return jax.numpy.asarray(true_len.max(), leaf.dtype) if name == "cache_index" else leaf

        return jax.tree_util.tree_map_with_path(fix, cache)

    def _build_generate(self, batch: int, bucket: int, max_new: int):
        jax, jnp = self._jax, self._jnp
        lax = jax.lax

        def run(params, tokens, true_len, max_new_arr, rng, temperature, top_k, eos_id):
            # dequant once per compiled call, amortised over every scan
            # step — measured 1.38x the fp decode rate on TPU, vs 0.48x
            # when dequant sat inside the step body (it does not fuse;
            # XLA re-materialised the fp tree every step)
            params = self._materialize(params)
            cache = self._init_cache(batch)
            last_logits, cache = self._prefill(params, cache, tokens, true_len)

            def sample(logits, rng):
                # temperature 0 -> greedy; top_k 0 -> full distribution
                greedy = jnp.argmax(logits, axis=-1)

                def draw(_):
                    scaled = logits / jnp.maximum(temperature, 1e-6)
                    k = jnp.where(top_k > 0, top_k, logits.shape[-1])
                    # mask everything below the k-th logit
                    kth = -jnp.sort(-scaled, axis=-1)
                    cutoff = jnp.take_along_axis(
                        kth, (k - 1)[None, None].repeat(logits.shape[0], 0), axis=-1
                    )[:, 0]
                    masked = jnp.where(scaled >= cutoff[:, None], scaled, -jnp.inf)
                    return jax.random.categorical(rng, masked, axis=-1)

                return lax.cond(temperature > 0, draw, lambda _: greedy, None)

            def step(carry, _):
                cache, logits, pos, rng, done, n = carry
                rng, step_rng = jax.random.split(rng)
                token = sample(logits, step_rng)
                token = jnp.where(done, eos_id, token)  # finished rows emit eos
                next_logits, cache = self._decode_step(params, cache, token[:, None], pos)
                done = done | (token == eos_id) | (n + 1 >= max_new_arr)
                return (cache, next_logits, pos + 1, rng, done, n + 1), token

            done0 = jnp.zeros((batch,), bool)
            (_, _, _, _, _, _), tokens_out = lax.scan(
                step,
                (cache, last_logits, true_len, rng, done0, jnp.zeros((), jnp.int32)),
                None,
                length=max_new,
            )
            return tokens_out.T  # (batch, max_new)

        return jax.jit(run)

    def generate(
        self,
        prompts: np.ndarray,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        eos_id: int = -1,
        seed: int = 0,
    ) -> np.ndarray:
        """prompts: (batch, prompt_len) int32 -> (batch, max_new) ids.

        Rows stop at ``eos_id`` (further slots filled with eos_id).
        """
        jax, jnp = self._jax, self._jnp
        prompts = np.atleast_2d(np.asarray(prompts, np.int32))
        batch, plen = prompts.shape
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise MicroserviceError(
                "max_new_tokens must be >= 1", status_code=400, reason="BAD_REQUEST"
            )
        bucket = next((b for b in self.prompt_buckets if b >= plen), None)
        # the cache holds max(bucket, plen + new) positions: prefill
        # writes `bucket` slots, decode continues from plen
        new_bucket = 1 << (max_new_tokens - 1).bit_length()  # pow2 ladder
        if bucket is None or max(bucket, plen + new_bucket) > self.max_len:
            # retry the exact count before rejecting: the bucketed scan
            # may overflow max_len when the exact request still fits
            if bucket is not None and max(bucket, plen + max_new_tokens) <= self.max_len:
                new_bucket = max_new_tokens
            else:
                raise MicroserviceError(
                    f"prompt {plen} + max_new {max_new_tokens} exceeds max_len {self.max_len}",
                    status_code=400,
                    reason="SEQUENCE_TOO_LONG",
                )
        padded = np.zeros((batch, bucket), np.int32)
        padded[:, :plen] = prompts
        # jit keys are bucketed in BOTH dimensions, so untrusted
        # per-request values can only ever hit O(log^2) compiled programs
        key = (batch, bucket, new_bucket)
        if key not in self._generate_jit:
            self._generate_jit[key] = self._build_generate(batch, bucket, new_bucket)
        run = self._generate_jit[key]
        out = run(
            self.params,
            jnp.asarray(padded),
            jnp.full((batch,), plen, jnp.int32),
            jnp.asarray(max_new_tokens, jnp.int32),
            jax.random.key(seed),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            jnp.asarray(eos_id, jnp.int32),
        )
        return np.asarray(out)[:, :max_new_tokens]


class GenerativeLM(TPUComponent):
    """Deployable generation component: token ids in, generated ids out.

    Parameters mirror TransformerLM's architecture knobs plus sampling
    defaults; ``model_uri`` loads a flax msgpack checkpoint (a trained
    TransformerLM parameter tree).
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
        temperature: float = 0.0,
        top_k: int = 0,
        eos_id: int = -1,
        model_uri: str = "",
        seed: int = 0,
        tp: int = 0,
        dp: int = 0,
        quantize: str = "",
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        self.config = dict(
            vocab_size=int(vocab_size), d_model=int(d_model),
            num_layers=int(num_layers), num_heads=int(num_heads),
            max_len=int(max_len),
        )
        # serving-mesh degrees (r11 tp, r19 dp): 0 defers to
        # SELDON_TPU_TP / SELDON_TPU_DP, shrinking the data axis
        # first on small hosts
        self.tp = int(tp)
        self.dp = int(dp)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = int(eos_id)
        self.model_uri = model_uri
        self.seed = int(seed)
        from seldon_core_tpu.ops.surgery import validate_quantize_mode

        self.quantize = validate_quantize_mode(quantize)  # fail at construction
        self.generator: Optional[Generator] = None
        import threading

        self._counter = 0
        self._counter_lock = threading.Lock()
        self._load_lock = threading.Lock()

    def load(self) -> None:
        # idempotent AND locked: the executor load()s on graph build
        # while concurrent first predicts lazy-load — an unlocked
        # check-then-act would let a second build swap the generator
        # (and its donated-buffer state) under an in-flight caller
        with self._load_lock:
            if self.generator is not None:
                return
            params = load_lm_params(self.model_uri, self.config, self.seed)
            self.generator = Generator(
                params, quantize=self.quantize, tp=self.tp or None,
                dp=self.dp or None,
                **self.config,
            )

    def predict(self, X, names, meta=None):
        if self.generator is None:
            self.load()
        meta = meta or {}
        tags = meta.get("tags", {})
        # sampling must actually sample: derive the key from the request
        # (tag override > puid > per-process counter), folded with the
        # deployment seed so runs are reproducible when pinned
        if "seed" in tags:
            request_seed = int(tags["seed"])
        else:
            puid = meta.get("puid", "")
            if puid:
                import zlib

                request_seed = zlib.crc32(puid.encode())
            else:
                with self._counter_lock:
                    self._counter += 1
                    request_seed = self._counter
        out = self.generator.generate(
            np.asarray(X),
            max_new_tokens=int(tags.get("max_new_tokens", self.max_new_tokens)),
            temperature=float(tags.get("temperature", self.temperature)),
            top_k=int(tags.get("top_k", self.top_k)),
            eos_id=self.eos_id,
            seed=self.seed ^ request_seed,
        )
        return out

    def class_names(self):
        return []
