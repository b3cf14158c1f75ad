"""What can be pinned of the chip bring-up without a chip: the device gate
of chip_smoke.py, the compile-cache helper, and the peak tables."""
import os
import shutil
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, **env):
    full = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu", **env}
    for key in [k for k, v in full.items() if v is None]:
        del full[key]
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_refuses_without_a_tpu(argv):
    res = _run(["chip_smoke.py", *argv], REPO)
    assert res.returncode == 1
    assert "no TPU visible" in res.stderr
    assert '"ok"' not in res.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    the script must fail, not report."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = _run(["chip_smoke.py"], str(tmp_path), PYTHONPATH=None)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


_PRINT_DIR = ("from dnn_page_vectors_tpu.utils.platform import "
              "enable_compile_cache; enable_compile_cache(); import jax; "
              "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_env_var_wins(tmp_path):
    res = _run(["-c", _PRINT_DIR], str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert res.stdout.strip() == str(tmp_path / "cc"), res.stderr


def test_compile_cache_default_is_fixed_in_checkout(tmp_path):
    """Unset: the same path inside the checkout from any cwd, any run."""
    outs = {_run(["-c", _PRINT_DIR], cwd,
                 JAX_COMPILATION_CACHE_DIR=None).stdout.strip()
            for cwd in (str(tmp_path), REPO)}
    assert outs == {os.path.join(REPO, ".jax_cache")}


def test_cli_writes_cache_where_env_says(tmp_path):
    cache = tmp_path / "cc"
    res = _run(["-m", "dnn_page_vectors_tpu.cli", "train", "--config",
                "cdssm_toy", "--workdir", str(tmp_path / "w"), "--steps", "2",
                "--set", "data.num_pages=64", "--set", "train.batch_size=8",
                "--set", "data.trigram_buckets=256",
                "--set", "model.embed_dim=16",
                "--set", "model.conv_channels=16",
                "--set", "model.out_dim=16"],
               str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert res.returncode == 0, res.stderr[-2000:]
    assert any(cache.iterdir()), "nothing was cached where the env says"


def _dev(kind, platform="tpu"):
    return types.SimpleNamespace(device_kind=kind, platform=platform)


@pytest.mark.parametrize("kind,flops,bw", [
    ("TPU v5 lite", 197e12, 819e9),
    ("TPU v5e", 197e12, 819e9),
    ("TPU v5p", 459e12, 2765e9),
    ("TPU v4", 275e12, 1228e9),
])
def test_peak_tables_by_device_kind(kind, flops, bw):
    from dnn_page_vectors_tpu.utils.flops import (
        device_peak_flops, device_peak_hbm_bps)
    assert device_peak_flops(_dev(kind)) == flops
    assert device_peak_hbm_bps(_dev(kind)) == bw


@pytest.mark.parametrize("kind", ["TPU v5", "TPU v7x", "TPU"])
def test_unlisted_tpu_kind_is_an_error(kind):
    """An unknown TPU generation never inherits a neighbour's peak."""
    from dnn_page_vectors_tpu.utils.flops import (
        device_peak_flops, device_peak_hbm_bps)
    with pytest.raises(ValueError, match="device_kind"):
        device_peak_flops(_dev(kind))
    with pytest.raises(ValueError, match="device_kind"):
        device_peak_hbm_bps(_dev(kind))


def test_cpu_has_no_peak():
    from dnn_page_vectors_tpu.utils.flops import (
        device_peak_flops, device_peak_hbm_bps)
    assert device_peak_flops(_dev("cpu", "cpu")) is None
    assert device_peak_hbm_bps(_dev("cpu", "cpu")) is None
