"""The fused Pallas decode lane as the pool-impl DEFAULT + the int8
KV pool with per-page scales (r18, ROADMAP 1).

Fast tier: the `auto` default's resolution rules, the `=0` escape
hatch's byte-for-byte lowering identity with the historical XLA gather
program, the int8 gating/accounting arithmetic, the container layout
(int8 pages + scale frames across the framing implementations), and
the kernel_active observability surface.

Slow tier: the standing parity matrix — greedy kernel-on vs kernel-off
bit-exactness at f32 across ring|pool × prefix × w8a8 × spec-verify ×
adapters (mirroring the r17 migration matrix), plus the int8-KV vs
native-pool top-1 agreement bound (quantisation is page-bounded, NOT
bit-exact — the test pins the honest claim).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models.paged import (
    PagedEngine,
    paged_capacity_streams,
    paged_hbm_accounting,
)
from seldon_core_tpu.models.transformer import TransformerLM

CFG = dict(vocab_size=64, d_model=32, num_layers=1, num_heads=2, max_len=256)


@pytest.fixture(scope="module")
def params():
    lm = TransformerLM(dtype=jnp.float32, **CFG)
    return lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **kw):
    base = dict(dtype=jnp.float32, page_size=8, max_slots=4, steps_per_call=4)
    base.update(kw)
    return PagedEngine(params, **CFG, **base)


def _prompts(n=4, seed=5):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, CFG["vocab_size"], size=(14 + 3 * i,)).astype(np.int32)
        for i in range(n)
    ]


def _decode_all(eng, prompts, max_new=12, **kw):
    streams = [eng.submit(p, max_new_tokens=max_new, **kw) for p in prompts]
    eng.run()
    out = np.stack([s.result for s in streams])
    eng.close()
    return out


# ---------------------------------------------------------------------------
# default flip (fast): auto resolution + the =0 escape hatch
# ---------------------------------------------------------------------------


class TestKernelDefaultFlip:
    def test_auto_resolves_off_the_tpu_backend(self, params, monkeypatch):
        """The r18 default is `auto`: kernel ON only when the backend is
        a TPU — a CPU host's pool engine must run the gather lane with
        no WARN (auto's silent fallback is the point of auto)."""
        monkeypatch.delenv("SELDON_TPU_PAGED_KERNEL", raising=False)
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        eng = _engine(params)
        try:
            expect = jax.default_backend() == "tpu"
            assert eng._kernel_active is expect
            assert eng.engine_stats()["kernel_active"] == int(expect)
        finally:
            eng.close()

    def test_force_activates_kernel_and_gauge(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "force")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        eng = _engine(params)
        try:
            assert eng._kernel_active is True
            assert eng.engine_stats()["kernel_active"] == 1
            _decode_all(eng, _prompts(2), max_new=4)
        finally:
            eng.close()

    def test_chunk_records_carry_kernel_active(self, params, monkeypatch):
        """Every flight-recorder chunk record names its decode lane —
        the post-hoc answer to 'was the kernel live for this chunk?'."""
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        eng = _engine(params)
        try:
            [eng.submit(p, max_new_tokens=4) for p in _prompts(2)]
            eng.run()
            recs = eng.engine_stats(detail=True)["recorder"]
            assert recs and all(r["kernel_active"] == 0 for r in recs)
        finally:
            eng.close()

    def test_kernel_gauges_are_bridge_mapped(self):
        """The engine_stats contract: both new keys must export through
        the Prometheus bridge (the observability contract test enforces
        the full mapping; this pins the canonical metric names)."""
        from seldon_core_tpu.utils.metrics import ENGINE_STATS_METRICS

        kind, name, _ = ENGINE_STATS_METRICS["kernel_active"]
        assert (kind, name) == ("gauge", "seldon_tpu_engine_kernel_active")
        kind, name, _ = ENGINE_STATS_METRICS["kv_dtype_int8"]
        assert (kind, name) == ("gauge", "seldon_tpu_engine_kv_dtype_int8")

    def test_kernel_off_recovers_xla_program_byte_for_byte(
        self, params, monkeypatch
    ):
        """`SELDON_TPU_PAGED_KERNEL=0` must lower the EXACT historical
        gather program: on a non-TPU backend `auto` resolves to the
        same lane, so the two lowerings must be byte-identical text —
        the default flip cannot perturb the fallback program."""
        if jax.default_backend() == "tpu":
            pytest.skip("auto resolves ON for TPU backends — the "
                        "contrast arm needs a non-TPU host")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")

        def lowered(mode):
            if mode is None:
                monkeypatch.delenv("SELDON_TPU_PAGED_KERNEL", raising=False)
            else:
                monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", mode)
            eng = _engine(params)
            try:
                return eng.lower_chunk(2, ((eng.max_slots, 4),)).as_text()
            finally:
                eng.close()

        assert lowered("0") == lowered(None)


# ---------------------------------------------------------------------------
# int8 KV pool gating + accounting (fast)
# ---------------------------------------------------------------------------


class TestInt8Gating:
    def test_int8_pool_engine_engages_and_reports(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", "int8")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
        eng = _engine(params)
        try:
            assert eng.cache.int8 is True
            assert eng.cache.pages_k.dtype == jnp.int8
            assert eng.cache.scales_k.dtype == jnp.float32
            assert eng.cache.scales_k.shape == (CFG["num_layers"], eng.num_pages)
            assert eng.engine_stats()["kv_dtype_int8"] == 1
        finally:
            eng.close()

    def test_int8_requires_pool_impl_falls_back_with_warn(
        self, params, monkeypatch, caplog
    ):
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", "int8")
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "ring")
        eng = _engine(params)
        try:
            assert eng.cache.int8 is False
            assert eng.cache.scales_k is None
            assert eng.cache.pages_k.dtype == jnp.float32
            assert "keeping the native pool dtype" in caplog.text
        finally:
            eng.close()

    def test_unknown_kv_dtype_raises_named(self, params, monkeypatch):
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", "fp4")
        with pytest.raises(ValueError, match="SELDON_TPU_KV_DTYPE"):
            _engine(params)


class TestInt8Accounting:
    KW = dict(num_layers=8, d_model=512, page_size=64, chunk_impl="pool",
              dtype_bytes=2)

    def test_int8_roughly_doubles_capacity(self):
        budget = 8 << 30
        bf16 = paged_capacity_streams(budget, 512, **self.KW)
        int8 = paged_capacity_streams(budget, 512, kv_dtype="int8", **self.KW)
        # pages at 1 byte/element + 64B/page of scales vs 2 bytes/element
        assert 1.9 <= int8 / bf16 <= 2.0

    def test_scale_table_is_priced_per_page(self):
        acct = paged_hbm_accounting(streams=1, ctx_len=512, kv_dtype="int8",
                                    **self.KW)
        pages = -(-512 // 64)
        tok = self.KW["num_layers"] * self.KW["d_model"] * 2  # 1 byte/elt
        scale = self.KW["num_layers"] * 2 * 4                 # 8B/page
        # logical bytes: the chip holds either pool layout unpadded
        assert acct["pool_bytes"] == int(pages * (64 * tok + scale))

    def test_ring_working_set_ignores_kv_dtype(self):
        """The ring impl never stores int8 (pool-impl-only lever): its
        gathered working set prices at the COMPUTE dtype either way."""
        kw = dict(self.KW, chunk_impl="ring")
        a = paged_hbm_accounting(streams=4, ctx_len=512, **kw)
        b = paged_hbm_accounting(streams=4, ctx_len=512, kv_dtype="int8", **kw)
        assert a["working_set_bytes"] == b["working_set_bytes"]


# ---------------------------------------------------------------------------
# int8 containers: scale frames across the framing implementations (fast)
# ---------------------------------------------------------------------------


def _int8_payload(rng, pages=3, ps=8, L=2, d=32):
    k = rng.integers(-127, 127, size=(L, pages, ps, d), dtype=np.int8)
    v = rng.integers(-127, 127, size=(L, pages, ps, d), dtype=np.int8)
    return {
        "prompt": np.arange(ps * pages - 2, dtype=np.int32),
        "last_logits": rng.random(64).astype(np.float32),
        "k": k, "v": v,
        "k_scales": rng.random((L, pages)).astype(np.float32) + 0.01,
        "v_scales": rng.random((L, pages)).astype(np.float32) + 0.01,
    }


class TestInt8Containers:
    def test_handoff_roundtrip_crc_clean(self):
        from seldon_core_tpu.codec import bufview

        p = _int8_payload(np.random.default_rng(0))
        out = bufview.unpack_kv_handoff(bufview.pack_kv_handoff(p))
        for key in ("k", "v", "k_scales", "v_scales"):
            np.testing.assert_array_equal(out[key], p[key])
        assert out["k_scales"].dtype == np.float32

    def test_migration_roundtrip_scales_appended(self):
        from seldon_core_tpu.codec import bufview

        p = _int8_payload(np.random.default_rng(1))
        p.update(tokens=np.arange(2, dtype=np.int32),
                 key_data=np.zeros(2, np.uint32), req_id="m1", seed=3)
        out = bufview.unpack_kv_migration(bufview.pack_kv_migration(p))
        np.testing.assert_array_equal(out["v_scales"], p["v_scales"])
        assert out["req_id"] == "m1"

    def test_int8_pages_without_scales_reject_named(self):
        from seldon_core_tpu.codec import bufview

        p = _int8_payload(np.random.default_rng(2))
        del p["k_scales"]
        with pytest.raises(bufview.PayloadError, match="k_scales"):
            bufview.pack_kv_handoff(p)

    def test_scales_without_int8_pages_reject_named(self):
        from seldon_core_tpu.codec import bufview

        p = _int8_payload(np.random.default_rng(3))
        p["k"] = p["k"].astype(np.float32)
        p["v"] = p["v"].astype(np.float32)
        with pytest.raises(bufview.PayloadError, match="int8"):
            bufview.pack_kv_handoff(p)

    def test_corrupt_int8_container_rejects_via_crc(self):
        from seldon_core_tpu.codec import bufview

        buf = bytearray(bufview.pack_kv_handoff(
            _int8_payload(np.random.default_rng(4))))
        buf[len(buf) // 2] ^= 0xFF
        with pytest.raises(bufview.PayloadError, match="CRC"):
            bufview.unpack_kv_handoff(bytes(buf))

    def test_native_framing_agrees_on_int8_scale_frames(self):
        """The C ABI (native/codec.cc) must walk an int8+scales
        container frame-by-frame to the same payload sizes and the same
        CRC the python lane computed — the three-implementation framing
        agreement extended to the r18 layout."""
        import ctypes

        from seldon_core_tpu.codec import bufview
        from seldon_core_tpu.native import get_lib

        lib = get_lib()
        if lib is None or not (hasattr(lib, "srt1_payload_bytes")
                               and hasattr(lib, "srt1_crc32c")):
            pytest.skip("native library not built")
        p = _int8_payload(np.random.default_rng(5))
        for key in ("k", "v", "k_scales", "v_scales"):
            frame = bufview.pack_frame(p[key])
            buf = (ctypes.c_uint8 * len(frame)).from_buffer_copy(frame)
            assert lib.srt1_payload_bytes(buf, len(frame)) == p[key].nbytes, key
        # the CRC the int8+scales container actually ships under must be
        # reproducible by the C lane over the identical covered bytes
        import struct

        container = bufview.pack_kv_handoff(p)
        magic, stored = struct.unpack("<II", container[-8:])
        assert magic == bufview.SRT1_CRC_MAGIC
        covered = container[:-8]
        assert lib.srt1_crc32c(covered, len(covered), 0) == stored
        assert bufview._crc32c_py(covered) == stored


# ---------------------------------------------------------------------------
# the standing parity matrix (slow): kernel-on vs kernel-off greedy
# bit-exactness at f32, every engine variant
# ---------------------------------------------------------------------------


def _ab_tokens(params, monkeypatch, engine_kw=None, submit_kw=None,
               chunk_impl="pool"):
    engine_kw = engine_kw or {}
    submit_kw = submit_kw or {}
    out = {}
    for mode in ("0", "force"):
        monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", mode)
        monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", chunk_impl)
        eng = _engine(params, **engine_kw)
        out[mode] = _decode_all(eng, _prompts(4), max_new=12, **submit_kw)
    return out["0"], out["force"]


@pytest.mark.slow
class TestKernelParityMatrix:
    @pytest.mark.parametrize("impl", ["ring", "pool"])
    @pytest.mark.parametrize("precision", ["", "w8a8"])
    @pytest.mark.parametrize("prefix", [True, False])
    def test_kernel_on_off_bit_exact(
        self, params, monkeypatch, impl, precision, prefix
    ):
        """Kernel on vs off must be a pure performance choice: greedy
        bit-exact at f32 in every chunk/precision/prefix variant (on
        the ring impl the knob is a no-op — same assertion)."""
        off, on = _ab_tokens(
            params, monkeypatch, chunk_impl=impl,
            engine_kw=dict(precision=precision, prefix_cache=prefix),
        )
        np.testing.assert_array_equal(off, on)

    def test_kernel_on_off_bit_exact_spec_verify(self, params, monkeypatch):
        off, on = _ab_tokens(
            params, monkeypatch,
            engine_kw=dict(speculative={"draft": "ngram", "draft_k": 2}),
        )
        np.testing.assert_array_equal(off, on)

    def test_kernel_on_off_bit_exact_adapters(self, params, monkeypatch):
        from seldon_core_tpu.models.registry import WeightRegistry
        from seldon_core_tpu.ops.lora import adapter_bytes, make_lora_params

        adapters = {
            f"t{i}": make_lora_params(
                100 + i, num_layers=CFG["num_layers"],
                d_model=CFG["d_model"], rank=2,
            )
            for i in range(2)
        }

        def tokens(mode):
            monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", mode)
            monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
            reg = WeightRegistry(budget_bytes=0)
            for name, ad in adapters.items():
                reg.register(name, (lambda a=ad: a),
                             bytes_hint=adapter_bytes(ad))
            eng = _engine(params, max_adapters=2, lora_rank=2,
                          weight_registry=reg)
            streams = [
                eng.submit(p, max_new_tokens=12,
                           adapter=("t0" if i % 2 else "t1"))
                for i, p in enumerate(_prompts(4))
            ]
            eng.run()
            out = np.stack([s.result for s in streams])
            eng.close()
            return out

        # a K-mixed adapter wave: the in-kernel BGMV fold vs the
        # gathered einsum pair must agree token-for-token
        np.testing.assert_array_equal(tokens("0"), tokens("force"))

    def test_int8_kv_top1_agreement_bound(self, params, monkeypatch):
        """Int8-KV is NOT bit-exact — per-page abs-max quantisation is
        a bounded perturbation.  The honest claim under test: greedy
        decode top-1 agreement with the native pool stays high — first
        tokens exact, full-sequence agreement >= 0.75 (measured 0.86 at
        this deterministic seed/config; random tiny-model logits sit
        far closer together than trained-model logits, so this is the
        pessimistic end of the bound)."""
        def tokens(kv):
            monkeypatch.setenv("SELDON_TPU_KV_DTYPE", kv)
            monkeypatch.setenv("SELDON_TPU_CHUNK_IMPL", "pool")
            monkeypatch.setenv("SELDON_TPU_PAGED_KERNEL", "0")
            eng = _engine(params, max_slots=8)
            return _decode_all(eng, _prompts(8, seed=11), max_new=16)

        native, int8 = tokens("bf16"), tokens("int8")
        assert (native[:, 0] == int8[:, 0]).all()
        assert (native == int8).mean() >= 0.75

    def test_int8_kv_kernel_vs_gather_bit_exact(self, params, monkeypatch):
        """Same quantised pool, two readers: the kernel's in-register
        dequant must agree with the gather lane's dequant token-for-
        token (quantisation error is identical — the READ path is what
        differs)."""
        off, on = _ab_tokens(params, monkeypatch)
        monkeypatch.setenv("SELDON_TPU_KV_DTYPE", "int8")
        off8, on8 = _ab_tokens(params, monkeypatch)
        np.testing.assert_array_equal(off, on)
        np.testing.assert_array_equal(off8, on8)
