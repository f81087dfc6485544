"""Operation and byte counts against hand-computed values at the
published shapes of both configurations."""
import json
from pathlib import Path

import pytest

from chipbench import counts, spec
from chipbench.percentiles import percentile, percentiles

CHECKOUT = Path(__file__).resolve().parents[2]
CONF = {n: json.loads((CHECKOUT / "chipbench" / "configs" / f"{n}.json").read_text())
        for n in ("qwen2-1.5b", "minicpm-2b")}
DENSE = spec.load_family(CONF["qwen2-1.5b"], CHECKOUT)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_qwen2_counts():
    c = CONF["qwen2-1.5b"]
    # q 12 heads + k, v 2 heads of 128; o; SwiGLU 3 x 1536 x 8960
    layer = 1536 * 16 * 128 + 1536 * 1536 + 3 * 1536 * 8960
    assert DENSE.layer_matmul_params(c) == layer == 46_792_704
    # 1.54 B parameters with the tied 151936 x 1536 embedding
    assert abs(28 * layer + 151936 * 1536 - 1.543e9) < 1e6
    assert DENSE.linear_flops(c) == 2 * 28 * layer
    assert counts.unembed_flops(c) == 2 * 1536 * 151936
    # two slots attending over 1000 and 3000 live keys
    dims = DENSE.attention_dims(c)
    f, b = counts.decode_attention_cost(dims, c, [1000, 3000])
    assert f == 28 * 4 * 12 * 128 * 4000
    assert b == 28 * 2 * (2 * 4000 * 2 * 128 + 2 * 2 * 12 * 128)
    f, b = counts.flash_attention_cost(dims, c, 2048)
    assert f == 28 * 2 * 12 * 128 * 2048 * 2049
    assert b == 28 * 2 * 2048 * 128 * (2 * 12 + 2 * 2)
    assert DENSE.prefill_flops(c, 2048) == \
        2048 * 2 * 28 * layer + f + 2 * 1536 * 151936
    assert counts.decode_step_flops(DENSE, c, [1000, 3000]) == \
        2 * (2 * 28 * layer + 2 * 1536 * 151936) + 28 * 4 * 12 * 128 * 4000


def test_minicpm_counts():
    c = CONF["minicpm-2b"]
    layer = 2304 * 108 * 64 + 2304 * 2304 + 3 * 2304 * 5760
    assert DENSE.layer_matmul_params(c) == layer == 61_046_784
    assert abs(40 * layer + 122753 * 2304 - 2.725e9) < 1e6
    # KV bytes per token: 40 layers x (K, V) x 36 heads x 64 x 2 B
    f, b = counts.decode_attention_cost(DENSE.attention_dims(c), c, [1])
    assert b == 40 * 2 * (2 * 1 * 36 * 64 + 2 * 36 * 64) and 40 * 2 * 36 * 64 * 2 == 368_640
    assert f == 40 * 4 * 36 * 64


def test_roofline_picks_the_larger_bound():
    assert counts.roofline_seconds(197e12, 0, PEAKS) == pytest.approx(1.0)
    assert counts.roofline_seconds(1, 819e9, PEAKS) == pytest.approx(1.0)
    # decode attention is bound by bytes: 4 ops per 4 bytes of K and V
    c = CONF["qwen2-1.5b"]
    dims = DENSE.attention_dims(c)
    f, b = counts.decode_attention_cost(dims, c, [4096] * 16)
    assert counts.roofline_seconds(f, b, PEAKS) == pytest.approx(b / 819e9)


@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
def test_percentiles_are_nearest_rank_as_the_program_defines(n):
    from repro.obs.percentiles import percentiles as program
    import numpy as np
    xs = list(np.random.default_rng(n).exponential(size=n))
    qs = (0.5, 0.9, 0.99)
    assert percentiles(xs, qs) == program(xs, qs)
    assert percentile(xs, 0.9) in xs
    assert percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    with pytest.raises(ValueError):
        percentile([], 0.5)
