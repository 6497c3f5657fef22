"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size, the
checks that decide ``ok``, its refusal to run off the chip, and where the
compile cache goes."""
import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

import chip_smoke
from repro import compile_cache
from repro.configs.logreg_paper import scaled


def test_main_refuses_cpu_backend(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_main_refuses_non_compiled_kernels(monkeypatch, capsys, mode):
    monkeypatch.setenv("REPRO_PALLAS", mode)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert "REPRO_PALLAS" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_phase_a_tiny():
    """The default path at the paper's density family, 16 lanes."""
    res = chip_smoke.phase_a(scaled(6400, 1000, density=0.005), 16, 3)
    r = [row["r_norm"] for row in res["rounds"]]
    assert len(r) == 3 and r[0] == 0.0 and r[2] < r[1]
    assert res["setup_s"] >= res["first_round_s"] > 0
    assert res["steady_s_per_round"] > 0
    assert all(row["z_nnz"] == -1 for row in res["rounds"])   # jnp z-update


def test_phase_b_tiny_interpret(monkeypatch):
    """Real Pallas kernel bodies on the CPU interpreter against the xla
    path; on the CPU the lowered solve holds no ``tpu_custom_call``."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    res = chip_smoke.phase_b(scaled(1200, 300, density=0.02), 4, 3, 5)
    assert res["mode"] == "interpret" and not res["custom_call"]
    assert res["gap"] <= chip_smoke.RTOL
    assert all(row["z_nnz"] >= 0 for row in res["pallas"]["rounds"])
    assert all(row["z_nnz"] == -1 for row in res["xla"]["rounds"])


def test_agreement_check():
    assert chip_smoke.check_agreement([0.0, 2.0, 1.0],
                                      [0.0, 2.0, 1.0005]) == pytest.approx(
        5e-4)
    with pytest.raises(chip_smoke.SmokeFailure, match="gap"):
        chip_smoke.check_agreement([0.0, 2.0, 1.0], [0.0, 2.0, 1.002])
    with pytest.raises(chip_smoke.SmokeFailure, match="gap"):
        chip_smoke.check_agreement([0.0, 2.0], [1e-3, 2.0])
    with pytest.raises(chip_smoke.SmokeFailure, match="lengths"):
        chip_smoke.check_agreement([0.0, 2.0], [0.0, 2.0, 1.0])


@pytest.mark.parametrize("r, s", [
    ([0.0, 3.0, 3.5], [1.0, 1.0, 1.0]),        # r_norm rises
    ([0.0, 3.0], [1.0, 1.0]),                  # too few rounds to tell
    ([0.0, 3.0, np.nan], [1.0, 1.0, 1.0]),     # non-finite r
    ([0.0, 3.0, 2.0], [1.0, np.inf, 1.0]),     # non-finite s
])
def test_residual_check_fails(r, s):
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_residuals(r, s)


def test_residual_check_passes_falling_r():
    chip_smoke.check_residuals([0.0, 3.0, 2.0, 1.5], [5.0, 4.0, 6.0, 1.0])


@pytest.fixture()
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_compile_cache_defaults_to_repo(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
