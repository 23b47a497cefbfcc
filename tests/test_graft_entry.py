"""Driver-gate tests.

The multichip dryrun is the only multi-chip correctness evidence the
CPU test suite can produce, so it must be hermetic to the accelerator
runtime: it provisions its own virtual CPU devices and never touches
the default backend, so an unusable accelerator cannot take it down.
"""

import os
import subprocess
import sys

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from __graft_entry__ import SMOKE_CHECKS  # noqa: E402

# The whole dry run takes ~90 s on the CPU, past the tier-1 per-test
# budget, so each case runs the sharded solves and a share of the smoke
# checks; together the shares cover every check.
_SHARES = (
    ("trace", "resilience", "batch", "serve", "fleet"),
    ("precond", "fmg", "fault_tolerance", "geometry", "bench_compare"),
    ("diff",),
    ("contracts",),
)


def test_shares_cover_every_smoke_check():
    shares = [name for share in _SHARES for name in share]
    assert sorted(shares) == sorted(SMOKE_CHECKS)


@pytest.mark.parametrize("checks", _SHARES, ids=lambda s: "+".join(s))
def test_dryrun_multichip_survives_dead_accelerator_runtime(checks):
    env = dict(os.environ)
    # Simulate an unusable accelerator: pin JAX to a platform that does
    # not exist, so any default-backend touch raises at backend init.
    env["JAX_PLATFORMS"] = "no_such_accelerator"
    # The dryrun must also provision its own virtual CPU devices.
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "from __graft_entry__ import dryrun_multichip; "
            f"dryrun_multichip(8, checks={checks!r}); print('hermetic-ok')",
        ],
        cwd=_REPO_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "hermetic-ok" in proc.stdout
