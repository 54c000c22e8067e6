"""``__graft_entry__.dryrun_multichip`` builds its own device count.

The dryrun needs an n-device CPU mesh whatever the caller's process looks
like, and ``--xla_force_host_platform_device_count`` is read once per
process — so the body runs in a child with its own ``XLA_FLAGS``. These
tests call it from a process whose environment asks for something else, and
check the bound on the child's run time.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, **env_overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.update(env_overrides)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=540)


def test_dryrun_sets_its_own_device_count():
    # the caller asks for ONE host device; the dryrun still gets its four
    proc = _run("import __graft_entry__ as g; g.dryrun_multichip(4)",
                XLA_FLAGS="--xla_force_host_platform_device_count=1",
                JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, f"dryrun failed:\n{proc.stdout[-3000:]}"
    assert "dryrun_multichip OK" in proc.stdout
    assert "mesh dp=2 tp=2 sp=1" in proc.stdout


def test_dryrun_timeout_is_an_error():
    """A child that does not finish inside DRYRUN_TIMEOUT raises, naming
    the bound, instead of hanging the caller."""
    code = (
        "import __graft_entry__ as g\n"
        "try:\n"
        "    g.dryrun_multichip(2)\n"
        "except RuntimeError as e:\n"
        "    assert 'exceeded' in str(e), e\n"
        "    print('TIMEOUT_OK')\n"
        "else:\n"
        "    raise SystemExit('dryrun did not time out')\n")
    # 0.5 s is less than a Python interpreter needs to import jax
    proc = _run(code, DRYRUN_TIMEOUT="0.5")
    assert proc.returncode == 0, proc.stdout[-2000:]
    assert "TIMEOUT_OK" in proc.stdout
