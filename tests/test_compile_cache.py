"""utils/compile_cache.py — env bootstrap semantics, and every batch CLI
caching where it should.

The helper must (a) default the cache dir to ``<repo>/.jax_cache`` and
create it, (b) never override an operator-exported value, and (c) stay
idempotent so a parent and its child can both call it. The CLI tests run
each batch entry point in a fresh CPU process and read back the directory
jax itself ended up configured with.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from mpi_cuda_largescaleknn_tpu.utils.compile_cache import (
    _REPO_ROOT, enable_persistent_cache)

_VARS = ("JAX_COMPILATION_CACHE_DIR",
         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
         "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES")
_DEFAULT = os.path.join(_REPO_ROOT, ".jax_cache")


def _clear(monkeypatch):
    # setitem-then-delitem (not bare delenv): setitem records the var's
    # ORIGINAL state — including absence — so values the code under test
    # writes into os.environ are rolled back at teardown instead of
    # leaking into later jax-importing tests
    for v in _VARS:
        monkeypatch.setitem(os.environ, v, "sentinel")
        monkeypatch.delitem(os.environ, v)


def test_defaults_to_repo_local_dir_and_creates_it(monkeypatch):
    _clear(monkeypatch)
    got = enable_persistent_cache()
    assert got == _DEFAULT == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert os.path.isdir(_DEFAULT)
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "1"
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "0"


def test_operator_export_wins(monkeypatch, tmp_path):
    _clear(monkeypatch)
    theirs = str(tmp_path / "operator")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", theirs)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "7")
    got = enable_persistent_cache()
    assert got == theirs == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "7"


def test_idempotent_and_repo_root_resolves(monkeypatch):
    _clear(monkeypatch)
    assert enable_persistent_cache() == enable_persistent_cache()
    # the default path is anchored at the repo root (where BENCHMARK.json
    # and chip_smoke.py live)
    assert os.path.isfile(os.path.join(_REPO_ROOT, "BENCHMARK.json"))
    assert os.path.isfile(os.path.join(_REPO_ROOT, "chip_smoke.py"))


def test_unwritable_dir_warns_but_does_not_raise(monkeypatch, capsys):
    _clear(monkeypatch)

    # forced failure, not a chmod'd dir: root (this container's uid)
    # ignores permission bits, which would leave the swallow path untested
    def _boom(*a, **kw):
        raise OSError("unwritable")

    import mpi_cuda_largescaleknn_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc.os, "makedirs", _boom)
    # helper must swallow the OSError (jax itself runs uncached) but must
    # TELL the operator: a silent cache loss repays every compile forever
    got = enable_persistent_cache()
    assert got == os.environ["JAX_COMPILATION_CACHE_DIR"]
    err = capsys.readouterr().err
    assert "compile cache" in err and "not writable" in err


_PROBE = """
import sys
from mpi_cuda_largescaleknn_tpu.cli.{mod} import main
assert "jax" not in sys.modules
rc = main(sys.argv[1:])
import jax
print("CACHE_DIR", jax.config.jax_compilation_cache_dir)
sys.exit(rc)
"""


@pytest.mark.parametrize("exported", [False, True])
@pytest.mark.parametrize("mod", ["unordered_main", "prepartitioned_main"])
def test_batch_cli_enables_cache_before_jax(mod, exported, tmp_path):
    pts = tmp_path / "p.float3"
    np.random.default_rng(0).random((64, 3), np.float32).tofile(pts)
    src = str(pts)
    if mod == "prepartitioned_main":
        src = str(tmp_path / "files.txt")
        with open(src, "w") as f:
            f.write(f"{pts}\n")
    env = {k: v for k, v in os.environ.items() if k not in _VARS}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO_ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    want = _DEFAULT
    if exported:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    r = subprocess.run(
        [sys.executable, "-c", _PROBE.format(mod=mod), src, "-k", "4",
         "-o", str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"CACHE_DIR {want}\n" in r.stdout
