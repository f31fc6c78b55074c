"""``chip_smoke.py`` on the CPU: its phases at a small size, its refusal
to pass without a TPU, and the compile-cache placement it starts with."""

import importlib.util
from pathlib import Path

import pytest

import jax

from repro import compile_cache
from repro.state import WindowOp, direct_aggregate

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def stream(smoke):
    keys, values = smoke.make_stream(6_144, 5_000, seed=0)
    direct = direct_aggregate(
        keys, WindowOp(agg="sum", value="payload", size=2_048),
        values=values)
    return keys, values, direct


@pytest.mark.parametrize("scheme", ("sg", "fg", "pkg", "dc", "wc", "fish"))
def test_smoke_phases_pass_small(smoke, stream, scheme):
    # windows of four feeds: partials anchor on tuples of earlier feeds
    keys, values, direct = stream
    obs = smoke.run_scheme(scheme, keys, values, direct, workers=16,
                           merge_workers=4, window=2_048, feed=512,
                           platform=jax.devices()[0].platform)
    assert obs["scheme"] == scheme
    assert len(obs["pane_flush_s"]) == 3  # one per window


def test_smoke_fails_on_a_wrong_device(smoke, stream):
    keys, values, direct = stream
    with pytest.raises(smoke.SmokeFailure, match="lives on"):
        smoke.run_scheme("fg", keys, values, direct, workers=8,
                         merge_workers=4, window=2_048, feed=2_048,
                         platform="tpu")


def test_smoke_exits_nonzero_without_tpu(smoke, monkeypatch, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    # an explicit cache directory keeps configure() off this process's
    # JAX config
    monkeypatch.setenv(compile_cache.ENV_VAR, str(REPO / ".jax_cache"))
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_compile_cache_leaves_explicit_dir_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
