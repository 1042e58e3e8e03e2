"""``pool_move_share_pct``: the share of busy device time in operations
shaped like the KV pool or a layer of it, on hand-made traces."""

import pytest

from harness import manifest
from layer_metrics.pool_move_share_pct import shape_of

CONFIG = manifest.load_json(
    manifest.os.path.join(manifest.BENCH_DIR, "configs", "gpt2-large.json"))


def read(ctx):
    return manifest.reader("layer_metrics", "pool_move_share_pct")(ctx)


def ops(**seconds):
    return {k: {"count": 1.0, "seconds": v} for k, v in seconds.items()}


# the ledger's rows for PR 24 (chat-saturated), and what does not count
PARENT = {
    "dynamic-update-slice_bf16_36_513_64_20_64_": 1.2,   # whole split pool
    "fusion_bf16_1_513_64_20_64_": 0.5,                  # one layer cut out
    "copy_bf16_513_64_1280_": 0.3,                       # that layer re-laid
    "copy-done_bf16_1_513_64_20_64_": 0.05,
    "copy_bf16_36_1_64_20_64_": 0.15,                    # a page block: no 513
    "pallas_kernel_f32_16_1_1280_": 0.5,
    "fusion_f32_32_": 0.03,
    "fusion_bf16_4_20_1024_2048_": 0.02,
}


def test_sums_pool_and_layer_shaped_operations_over_busy_time():
    ctx = {"trace": {"busy_s": 3.0, "ops": ops(**PARENT)}, "config": CONFIG}
    assert read(ctx) == pytest.approx(100.0 * (1.2 + 0.5 + 0.3 + 0.05) / 3.0)


def test_an_in_place_write_on_the_flat_pool_still_counts():
    flat = {"dynamic-update-slice_bf16_36_513_64_1280_": 0.06,
            "fusion_bf16_36_513_64_1280_": 0.03,
            "pallas_kernel_f32_32_1_1280_": 0.9}
    ctx = {"trace": {"busy_s": 3.0, "ops": ops(**flat)}, "config": CONFIG}
    assert read(ctx) == pytest.approx(3.0)


def test_zero_when_nothing_pool_shaped_ran():
    ctx = {"trace": {"busy_s": 2.0, "ops": ops(**{
        "pallas_kernel_f32_16_1_1280_": 0.5,
        "fusion_bf16_513_1280_": 0.1,      # 513 without the page size beside it
        "fusion_bf16_64_513_": 0.1,        # the two dims the other way round
    })}, "config": CONFIG}
    assert read(ctx) == 0.0


@pytest.mark.parametrize("ctx", [
    {"trace": None, "config": CONFIG},
    {"config": CONFIG},
    {"trace": {"devices": 0}, "config": CONFIG},
    {"trace": {"busy_s": 0.0, "ops": {}}, "config": CONFIG},
    {"trace": {"busy_s": 3.0, "ops": ops(**PARENT)}, "config": {}},
    {"trace": {"busy_s": 3.0, "ops": ops(**PARENT)},
     "config": {"deployment": {"predictors": [{"graph": {"parameters": []}}]}}},
])
def test_none_without_a_trace_or_a_paged_pool(ctx):
    assert read(ctx) is None


@pytest.mark.parametrize("key,dims", [
    ("dynamic-update-slice_bf16_36_513_64_20_64_", [36, 513, 64, 20, 64]),
    ("pallas_kernel_f32_16_1_1280_", [16, 1, 1280]),
    ("copy-done_bf16_1_513_64_20_64_", [1, 513, 64, 20, 64]),
    ("fusion_f32_32_", [32]),
    ("fusion_pred__", []),
    ("host-side-name", []),
])
def test_shape_of_a_stable_op_name(key, dims):
    assert shape_of(key) == dims
