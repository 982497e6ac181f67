"""chip_smoke.py's contract off the chip, and the compile-cache helper.

The smoke itself only passes on a TPU (the driver and the builder run it
there); what tier-1 can pin is that it REFUSES everywhere else, that one
failing phase neither stops the others nor ends in exit status 0, and that
the compile cache goes where the environment says."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + argv, capture_output=True,
                          text=True, timeout=timeout, cwd=REPO, env=env)


def test_default_invocation_refuses_without_a_tpu():
    p = _run([SMOKE])
    assert p.returncode == 4
    assert p.stdout == ""               # no result, no phase line, nothing
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr


# fake phases in place of the real ones: the driver loop is what is under
# test, and the real phases are minutes of compile even at toy width
_DRIVER = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs
from deeplearning4j_tpu.common import native_ops
native_ops.build = lambda force=False: (True, "stubbed for the test")
ran = []
def boom(cfg):
    ran.append("boom")
    raise cs.SmokeFailure("checked value was wrong")
def fine(cfg):
    ran.append("fine")
    return {{"checked": "nothing"}}
cs.PHASES = (("first", fine, 1), ("second", boom, 1), ("third", fine, 1),
             ("needs_many", fine, 10 ** 6))
rc = cs.main(["--rehearsal"] + sys.argv[1:])
print("RAN " + ",".join(ran))
sys.exit(rc)
"""


def _drive(*args):
    p = _run(["-c", _DRIVER.format(repo=REPO), *args])
    lines = p.stdout.strip().splitlines()
    ran = lines.pop()
    assert ran.startswith("RAN ")
    return p.returncode, [json.loads(l) for l in lines], ran[4:].split(",")


def test_failing_phase_keeps_the_rest_running_and_the_exit_nonzero():
    rc, lines, ran = _drive()
    assert ran == ["fine", "boom", "fine"]      # the phase after the failure ran
    assert rc == 1
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    assert by_phase["first"]["pass"] and by_phase["third"]["pass"]
    assert not by_phase["second"]["pass"]
    assert "checked value was wrong" in by_phase["second"]["error"]
    assert by_phase["needs_many"]["ran"] is False
    assert lines[-1]["ok"] is False
    assert all(l["rehearsal"] for l in lines)   # every line is labelled


def test_rehearsal_and_partial_runs_never_reach_the_passing_verdict():
    rc, lines, ran = _drive("--phases", "first,third")
    assert ran == ["fine", "fine"]
    assert rc == 5                       # what ran passed; still not exit 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["all_phases_run_passed"] is True


def test_cache_helper_leaves_the_environments_directory_alone(monkeypatch):
    import jax

    from deeplearning4j_tpu.common import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(cc.ENV_VAR, "/some/dir")
    assert cc.enable_compile_cache() == "/some/dir"
    assert calls == []                   # nothing set in code
    monkeypatch.delenv(cc.ENV_VAR)
    assert cc.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir",
                      os.path.join(REPO, ".jax_cache"))]


def test_cache_entries_counts_programs_not_access_stamps(tmp_path):
    from deeplearning4j_tpu.common.compile_cache import cache_entries
    assert cache_entries(str(tmp_path / "absent")) == 0
    for name in ("jit_f-abc-cache", "jit_f-abc-atime", "jit_g-def-cache"):
        (tmp_path / name).write_bytes(b"x")
    assert cache_entries(str(tmp_path)) == 2


@pytest.mark.slow
def test_rehearsal_runs_every_phase_at_toy_width():
    p = _run([SMOKE, "--rehearsal"], timeout=1500)
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    assert p.returncode == 5, p.stdout[-3000:] + p.stderr[-3000:]
    assert all(l["pass"] for l in lines if "pass" in l)
    assert lines[-1]["ok"] is False and lines[-1]["why"] == "rehearsal"
