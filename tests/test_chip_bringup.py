"""What keeps the chip path honest without a chip: the compile-cache
helper's one fixed location, the device-kind peak table that refuses an
unknown chip, and ``chip_smoke.py`` refusing to run, or to print a result,
anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import hw
from repro.launch.compile_cache import compile_cache_dir

ROOT = Path(__file__).resolve().parents[1]


def _run(args, *, cwd=ROOT, extra_env=None, timeout=120):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_compile_cache_dir_respects_the_environment():
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/srv/cc"}) == \
        ("/srv/cc", False)


def test_compile_cache_dir_is_fixed_inside_the_checkout():
    assert compile_cache_dir({}) == (str(ROOT / ".jax_cache"), True)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_enable_compile_cache_points_jax_at_one_directory(env_dir, tmp_path):
    extra = {} if env_dir is None else \
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / env_dir)}
    r = _run(["-c", "from repro.launch.compile_cache import "
              "enable_compile_cache as e; p = e(); import jax; "
              "print(p); print(jax.config.jax_compilation_cache_dir)"],
             extra_env=extra)
    assert r.returncode == 0, r.stderr
    want = str(ROOT / ".jax_cache") if env_dir is None \
        else str(tmp_path / env_dir)
    assert r.stdout.split() == [want, want]


def test_chip_peaks_are_keyed_by_device_kind():
    v5e = hw.chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    assert hw.TPU_PEAK_FLOPS == v5e.bf16_flops
    with pytest.raises(ValueError, match="no published peaks"):
        hw.chip_peaks("cpu")


def test_chip_smoke_refuses_the_cpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_refuses_a_directory_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run(["chip_smoke.py", "--chips", "4"], cwd=tmp_path,
             extra_env={"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "sources are missing" in r.stderr
