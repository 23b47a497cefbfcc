"""chip_smoke.py off the chip, and where the program keeps what it writes.

``chip_smoke.py`` must refuse to run without a TPU, so here its phases
are driven directly, at small grids on the CPU mesh (Pallas kernels in
interpret mode): the same control flow, checks and records the chip run
takes. The placement tests pin the one way each on-disk artefact is
found: the compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or sits
at ``<repo>/.jax_cache/``; the autotune registry is always in the
checkout.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import chip_smoke  # noqa: E402


def _env(**overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO_ROOT, **overrides)
    return env


def test_chip_smoke_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=_REPO_ROOT, env=_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_ladder_phase_on_cpu():
    rec = chip_smoke.ladder((40, 40), "resident", 50, jax.devices()[0])
    assert rec["ok"], rec
    assert rec["engine"] == rec["select_engine"] == "resident"
    assert rec["iters"] == 50 and not rec["fallback_warnings"]
    assert rec["degrade_events"] == 0
    # the oracle-free form gates on l2 against the xla engine instead
    rec = chip_smoke.ladder((40, 40), "resident", None, jax.devices()[0])
    assert rec["ok"], rec
    assert rec["ref_engine"] == "xla" and abs(rec["l2_rel_to_ref"]) < 0.10


def test_chip_smoke_ladder_phase_fails_on_wrong_engine():
    rec = chip_smoke.ladder((40, 40), "xl", 50, jax.devices()[0])
    assert not rec["ok"]


def test_chip_smoke_serve_phase_on_cpu():
    rec = chip_smoke.serve(jax.devices()[0], grid=(40, 40), oracle=50,
                           requests=3, lanes=2)
    assert rec["ok"], rec
    assert rec["completed"] == 3 and rec["iters"] == [50, 50, 50]


def test_chip_smoke_sharded_phase_on_cpu(capsys):
    oks = chip_smoke.sharded(jax.devices()[:4], grid=(40, 40))
    recs = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    assert oks == [True, True, True], recs
    assert [r["phase"] for r in recs] == [
        "sharded-reference", "sharded-xla", "sharded-fused"]
    for rec in recs[1:]:
        assert rec["mesh"] == [2, 2] and len(rec["w_devices"]) == 4
        assert rec["iters"] == rec["ref_iters"] == 50
    # the 41x41 node grid pads to 42x42: one 21x21 block per device
    assert recs[1]["operand_shard_shape"] == [21, 21]


_CACHE_PROBE = """
import os, sys, jax, jax.numpy as jnp
import poisson_ellipse_tpu.runtime.compile_cache as cc
from poisson_ellipse_tpu.runtime import autotune
assert cc.DEFAULT_CACHE_DIR == os.path.join(sys.argv[1], ".jax_cache")
assert autotune.registry_path() == os.path.join(
    sys.argv[1], ".autotune", "registry.json")
cc.DEFAULT_CACHE_DIR = sys.argv[2]  # stand-in for <repo>/.jax_cache
print(cc.enable_persistent_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_placement(tmp_path, env_dir):
    default, chosen = tmp_path / "default", tmp_path / "chosen"
    chosen.mkdir()
    env = _env(**({"JAX_COMPILATION_CACHE_DIR": str(chosen)}
                  if env_dir else {}))
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, _REPO_ROOT, str(default)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    used, unused = (chosen, default) if env_dir else (default, chosen)
    assert proc.stdout.strip() == str(used)
    assert os.listdir(used), "the compile was not cached"
    assert not (unused.exists() and os.listdir(unused))


def test_autotune_registry_ignores_the_compile_cache_dir(monkeypatch,
                                                          tmp_path):
    from poisson_ellipse_tpu.runtime import autotune

    before = autotune.registry_path()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert autotune.registry_path() == before
    assert before.startswith(_REPO_ROOT + os.sep)
