"""The operation and byte counts of bench.costs against counts worked
out by hand for the two configurations as run (8 layers each)."""
import bench_fixtures  # noqa: F401  (puts the benchmark on sys.path)

import pytest

from bench import costs, hw, spec

# per layer: q, k, v projections, the output projection, SwiGLU's three
# matrices; then the head
YI_LAYER = 4096 * (32 + 2 * 4) * 128 + 32 * 128 * 4096 + 3 * 4096 * 11008
GLM_LAYER = 4096 * (32 + 2 * 2) * 128 + 32 * 128 * 4096 + 3 * 4096 * 13696


@pytest.mark.parametrize("name,matmul", [
    ("yi-9b", 8 * 173_015_040 + 4096 * 64_000),
    ("chatglm3-6b", 8 * 203_948_032 + 4096 * 65_024)])
def test_matmul_params_by_hand(name, matmul):
    w = spec.work(spec.load_config(name))
    assert YI_LAYER == 173_015_040 and GLM_LAYER == 203_948_032
    assert w["matmul_params"] == matmul
    assert costs.norm_params(w) == 17 * 4096
    assert w["attn_layers"] == 8 and w["norms"] == [[4096, 17]]


def test_step_flops_yi():
    d = spec.work(spec.load_config("yi-9b"))
    kv = [100] * 8
    # 2 FLOPs per weight per row, plus 4 * 32 heads * 128 * 800 positions
    # of attention in each of the 8 layers
    want = 2 * 1_646_264_320 * 8 + 8 * 4 * 32 * 128 * 800
    assert costs.step_flops(d, kv) == want == 26_445_086_720


def test_step_bytes_chatglm():
    d = spec.work(spec.load_config("chatglm3-6b"))
    kv = [10, 20]
    weights = (1_897_922_560 + 17 * 4096) * 2
    embed = 2 * 4096 * 2
    # K and V of 2 KV heads x 128 in bf16 = 1 KiB per position and layer;
    # 30 live positions read and 2 new ones written in each of 8 layers
    cache = 8 * 1024 * (30 + 2)
    assert costs.step_bytes(d, kv) == weights + embed + cache


def test_decode_attn_call_yi():
    d = spec.work(spec.load_config("yi-9b"))
    flops, nbytes = costs.decode_attn_call(d, [2048, 1])
    assert flops == 4 * 32 * 128 * 2049
    # q in and out: 32 x 128 bf16 each per row; K/V: 2 KiB per position
    assert nbytes == 2 * 2 * 8192 + 2048 * 2049


def test_rmsnorm_call_and_least_time():
    d = spec.work(spec.load_config("yi-9b"))
    flops, nbytes = costs.rmsnorm_call(d["d_model"], 8)
    assert (flops, nbytes) == (4 * 8 * 4096, 17 * 4096 * 2)
    v5e = hw.peaks("TPU v5 lite")
    assert costs.least_seconds(flops, nbytes, v5e) == nbytes / 819e9
    assert costs.least_seconds(197e12, 1, v5e) == 1.0


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no peaks"):
        hw.peaks("TPU v99")
