"""Guard the assigned architecture configs against drift.

Every number below is from the assignment table (citations in each config
module). If a config module changes these, the reproduction is no longer
faithful — these tests are the contract.
"""

import pytest

from repro.configs import (
    ARCH_IDS,
    INPUT_SHAPES,
    all_configs,
    config_for_shape,
    get_config,
)

# arch: (L, d_model, H, kv, d_ff, vocab, family)
ASSIGNED = {
    "gemma-2b": (18, 2048, 8, 1, 16384, 256000, "dense"),
    "yi-9b": (48, 4096, 32, 4, 11008, 64000, "dense"),
    "command-r-35b": (40, 8192, 64, 8, 22528, 256000, "dense"),
    "zamba2-7b": (81, 3584, 32, 32, 14336, 32000, "hybrid"),
    "mamba2-780m": (48, 1536, 0, 0, 0, 50280, "ssm"),
    "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064, "vlm"),
    "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768, "moe"),
    "olmo-1b": (16, 2048, 16, 16, 8192, 50304, "dense"),
    "arctic-480b": (35, 7168, 56, 8, 4864, 32000, "moe"),
    "musicgen-large": (48, 2048, 32, 32, 8192, 2048, "audio"),
    "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352, "hybrid"),
}

# published parameter counts (total, rough band) to sanity-check param_count()
PUBLISHED_PARAMS = {
    "gemma-2b": (2.0e9, 3.2e9),
    "yi-9b": (8.0e9, 10e9),
    "command-r-35b": (30e9, 40e9),
    "zamba2-7b": (6.3e9, 8.5e9),
    "mamba2-780m": (0.6e9, 0.95e9),
    "phi-3-vision-4.2b": (3.3e9, 4.6e9),
    "mixtral-8x22b": (120e9, 150e9),
    "olmo-1b": (0.9e9, 1.5e9),
    "arctic-480b": (400e9, 520e9),
    "musicgen-large": (2.5e9, 3.6e9),  # MusicGen-large is 3.3B total
    "granite-4.0-h-micro": (3.1e9, 3.3e9),  # "3B"; 3.19B from its config
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_assigned_numbers_exact(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v, fam = ASSIGNED[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == h
    if h:
        assert cfg.num_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v
    assert cfg.family == fam
    assert cfg.source, f"{arch} missing citation"


def test_family_specifics():
    assert get_config("gemma-2b").head_dim == 256
    assert get_config("gemma-2b").hidden_act == "gelu"         # GeGLU
    assert get_config("gemma-2b").num_kv_heads == 1            # MQA
    assert get_config("olmo-1b").norm == "nonparametric"
    assert get_config("command-r-35b").use_bias is False
    mix = get_config("mixtral-8x22b")
    assert mix.moe.num_experts == 8 and mix.moe.top_k == 2
    assert mix.sliding_window is not None                       # SWA native
    arc = get_config("arctic-480b")
    assert arc.moe.num_experts == 128 and arc.moe.top_k == 2
    assert arc.moe.dense_residual
    zam = get_config("zamba2-7b")
    assert zam.ssm.d_state == 64 and zam.hybrid_attn_every > 0
    mam = get_config("mamba2-780m")
    assert mam.ssm.d_state == 128
    mus = get_config("musicgen-large")
    assert mus.num_codebooks == 4 and mus.modality == "audio"
    phi = get_config("phi-3-vision-4.2b")
    assert phi.modality == "vlm" and phi.num_patches > 0
    gra = get_config("granite-4.0-h-micro")
    assert gra.period == 10 and gra.layer_types[:10].index("attention") == 5
    assert [i for i, t in enumerate(gra.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    assert gra.position_embedding_type == "nope" and gra.ssm.conv_bias


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_count_in_published_band(arch):
    cfg = get_config(arch)
    lo, hi = PUBLISHED_PARAMS[arch]
    n = cfg.param_count()
    assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]B"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_reduction_rules(arch):
    s = get_config(arch + "-smoke")
    # a per-layer mixer pattern keeps one whole period
    assert s.num_layers <= (get_config(arch).period if s.layer_types else 2)
    assert s.d_model <= 512
    if s.moe is not None:
        assert s.moe.num_experts <= 4
    assert s.family == get_config(arch).family


def test_moe_active_params_much_smaller():
    for arch in ("mixtral-8x22b", "arctic-480b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.55 * cfg.param_count()


def test_long500k_policy():
    """long_500k must resolve to a sub-quadratic config for every arch."""
    for arch in ARCH_IDS:
        cfg = config_for_shape(arch, "long_500k")
        ok = (cfg.family == "ssm"
              or (cfg.sliding_window is not None
                  and cfg.sliding_window <= 8192)
              or cfg.family == "hybrid")
        assert ok, f"{arch} resolves to quadratic attention at 500k: {cfg.name}"


def test_input_shapes_assigned():
    s = INPUT_SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)


def test_all_configs_resolve():
    cfgs = all_configs()
    assert len(cfgs) == 11
    assert get_config("yi-9b-swa4096").sliding_window == 4096
    with pytest.raises(KeyError):
        get_config("not-a-model")
