"""Dry-run machinery unit tests (no 512-device compiles here — those run via
``python -m repro.launch.dryrun``; artifacts land in artifacts/dryrun/)."""

import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config
from repro.launch.specs import SHAPES, applicability, input_specs


def test_40_cells_accounting():
    """11 archs × 4 shapes = 44 cells; 35 runnable + 9 documented skips."""
    runnable, skipped = [], []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES:
            ok, reason = applicability(cfg, shape)
            (runnable if ok else skipped).append((arch, shape, reason))
    assert len(runnable) + len(skipped) == 44
    assert len(runnable) == 35
    skips = {(a, s) for a, s, _ in skipped}
    assert ("hubert-xlarge", "decode_32k") in skips
    assert ("hubert-xlarge", "long_500k") in skips
    for dense in ("granite-3-8b", "qwen1.5-0.5b", "granite-8b", "deepseek-7b",
                  "dbrx-132b", "qwen2-vl-2b", "moonlight-16b-a3b"):
        assert (dense, "long_500k") in skips, dense
    # sub-quadratic archs run long_500k
    for a in ("xlstm-350m", "mixtral-8x22b", "jamba-1.5-large-398b"):
        assert (a, "long_500k") not in skips, a


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_shapes(arch):
    cfg = get_config(arch)
    b = input_specs(cfg, "train_4k")
    if cfg.input_mode == "embeds":
        assert b["embeds"].shape == (256, 4096, cfg.d_model)
    else:
        assert b["tokens"].shape == (256, 4096)
        assert b["tokens"].dtype == jnp.int32
    assert b["labels"].shape == (256, 4096)
    p = input_specs(cfg, "prefill_32k")
    key = "embeds" if cfg.input_mode == "embeds" else "tokens"
    assert p[key].shape[:2] == (32, 32768)
    assert "labels" not in p
    d = input_specs(cfg, "decode_32k")
    assert d["tokens"].shape == (128,)
    assert d["pos"].shape == ()


def test_mrope_archs_get_position_specs():
    cfg = get_config("qwen2-vl-2b")
    b = input_specs(cfg, "train_4k")
    assert b["positions"].shape == (256, 4096, 3)


def test_inner_scan_correction_only_for_recurrent():
    from repro.launch.dryrun import inner_scan_correction

    dense = get_config("granite-3-8b")
    assert inner_scan_correction(dense, 256, 4096, "train", 256) == 0.0
    jamba = get_config("jamba-1.5-large-398b")
    c = inner_scan_correction(jamba, 256, 4096, "train", 256)
    assert c > 0
    assert inner_scan_correction(jamba, 128, 32768, "decode", 256) == 0.0
    xlstm = get_config("xlstm-350m")
    assert inner_scan_correction(xlstm, 256, 4096, "prefill", 256) > 0


def test_swa_cache_is_window_sized():
    """long_500k for mixtral allocates a ring cache of the window, not 524k."""
    import jax

    from repro.models import Model

    cfg = get_config("mixtral-8x22b")
    model = Model(cfg, remat=False)
    cache = jax.eval_shape(lambda: model.init_cache(1, 524288))
    k = cache[0]["k"]
    assert k.shape[2] == cfg.window  # (periods, B, window, KV, hd)


def test_production_mesh_shapes():
    from repro.launch.mesh import MULTI_POD_SHAPE, POD_SHAPE

    assert POD_SHAPE == (16, 16)
    assert MULTI_POD_SHAPE == (2, 16, 16)
