"""Architecture registry: exact assigned configs, cell accounting."""

import pytest

from repro.configs import ARCHS, SHAPES, cells, get_config, list_archs
from repro.core.tuning import active_param_count, param_count_estimate

ASSIGNED = {
    "whisper-tiny": dict(num_layers=4, d_model=384, num_heads=6,
                         num_kv_heads=6, d_ff=1536, vocab_size=51865),
    "mistral-large-123b": dict(num_layers=88, d_model=12288, num_heads=96,
                               num_kv_heads=8, d_ff=28672, vocab_size=32768),
    "nemotron-4-340b": dict(num_layers=96, d_model=18432, num_heads=96,
                            num_kv_heads=8, d_ff=73728, vocab_size=256000),
    "stablelm-1.6b": dict(num_layers=24, d_model=2048, num_heads=32,
                          num_kv_heads=32, d_ff=5632, vocab_size=100352),
    "deepseek-7b": dict(num_layers=30, d_model=4096, num_heads=32,
                        num_kv_heads=32, d_ff=11008, vocab_size=102400),
    "xlstm-1.3b": dict(num_layers=48, d_model=2048, num_heads=4,
                       num_kv_heads=4, d_ff=0, vocab_size=50304),
    "llava-next-34b": dict(num_layers=60, d_model=7168, num_heads=56,
                           num_kv_heads=8, d_ff=20480, vocab_size=64000),
    "granite-moe-3b-a800m": dict(num_layers=32, d_model=1536, num_heads=24,
                                 num_kv_heads=8, d_ff=512, vocab_size=49155,
                                 num_experts=40, experts_per_token=8),
    "dbrx-132b": dict(num_layers=40, d_model=6144, num_heads=48,
                      num_kv_heads=8, d_ff=10752, vocab_size=100352,
                      num_experts=16, experts_per_token=4),
    "zamba2-7b": dict(num_layers=81, d_model=3584, num_heads=32,
                      num_kv_heads=32, d_ff=14336, vocab_size=32000,
                      ssm_state=64),
    "deepseek-v2-lite": dict(num_layers=27, d_model=2048, num_heads=16,
                             d_ff=10944, vocab_size=102400, num_experts=64,
                             experts_per_token=6, moe_d_ff=1408,
                             shared_experts=2, first_dense_layers=1,
                             kv_lora_rank=512, qk_nope_head_dim=128,
                             qk_rope_head_dim=64, v_head_dim=128,
                             norm_topk=False),
}


def test_all_assigned_archs_registered():
    archs = list_archs()
    for a in ASSIGNED:
        assert a in archs
    assert "lulesh-dash" in archs  # the paper's own app


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_exact_assigned_numbers(arch):
    cfg = get_config(arch)
    for key, val in ASSIGNED[arch].items():
        assert getattr(cfg, key) == val, (arch, key, getattr(cfg, key), val)


def test_shapes_match_assignment():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].seq_len == 32768
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


def test_cell_accounting_40():
    all_cells = cells(include_skipped=True)
    assert len(all_cells) == 44  # 11 archs x 4 shapes
    skipped = [c for c in all_cells if c[2]]
    # 9 full-attention archs skip long_500k; ssm/hybrid run it
    assert len(skipped) == 9
    for arch, shape, _ in skipped:
        assert shape == "long_500k"
        assert not get_config(arch).sub_quadratic
    runnable = cells()
    assert len(runnable) == 35


@pytest.mark.parametrize("arch,target", [
    ("mistral-large-123b", 123e9), ("nemotron-4-340b", 340e9),
    ("dbrx-132b", 132e9), ("deepseek-7b", 7e9), ("stablelm-1.6b", 1.6e9),
    ("xlstm-1.3b", 1.3e9), ("zamba2-7b", 7e9), ("llava-next-34b", 34e9),
    ("granite-moe-3b-a800m", 3.4e9), ("deepseek-v2-lite", 15.7e9),
])
def test_param_counts_near_nameplate(arch, target):
    n = param_count_estimate(get_config(arch))
    assert 0.7 * target < n < 1.45 * target, (arch, n / 1e9)


def test_moe_active_params():
    g = get_config("granite-moe-3b-a800m")
    active = active_param_count(g)
    total = param_count_estimate(g)
    assert active < total
    # ~800M active per the model name (embeddings included here)
    assert 0.4e9 < active < 1.4e9, active / 1e9
