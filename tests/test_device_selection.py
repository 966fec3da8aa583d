"""How the device is chosen and where the compile cache goes (ISSUE 22).

The device is whatever ``jax.default_backend()`` says: no child process at
import, no verdict on disk, and a failed accelerator init raises instead of
continuing on the CPU. The persistent compile cache is placed from outside
by ``JAX_COMPILATION_CACHE_DIR`` or sits at one fixed path in the checkout.
Each case needs a fresh interpreter (jax reads its environment at import),
so these drive children — CPU children: ``JAX_PLATFORMS=cpu`` is inherited
from conftest.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code, env=None, argv=(), timeout=300):
    full = dict(os.environ)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full["PYTHONPATH"] = REPO + os.pathsep + full.get("PYTHONPATH", "")
    full.update(env or {})
    return subprocess.run([sys.executable, "-c", code, *argv], env=full,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=timeout)


def _last_json(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_CACHE_CHILD = """
import json, sys
import jax
import mxnet_tpu as mx
got = mx.context.enable_compilation_cache(*sys.argv[1:])
print(json.dumps({"returned": got,
                  "config": jax.config.jax_compilation_cache_dir,
                  "tuning": mx.context.tuning_cache_path()}))
"""


@pytest.mark.parametrize("arg", [None, "elsewhere"],
                         ids=["no_argument", "with_argument"])
def test_cache_placed_from_outside_is_left_alone(tmp_path, arg):
    placed = str(tmp_path / "placed")
    argv = () if arg is None else (str(tmp_path / arg),)
    out = _last_json(_child(_CACHE_CHILD,
                            {"JAX_COMPILATION_CACHE_DIR": placed}, argv))
    assert out["config"] == placed
    assert out["returned"] == placed
    assert out["tuning"] == os.path.join(placed, "tuning_cache.json")


def test_default_cache_is_one_fixed_path_in_the_checkout():
    first = _last_json(_child(_CACHE_CHILD))
    second = _last_json(_child(_CACHE_CHILD))
    assert first == second
    assert first["config"] == first["returned"] \
        == os.path.join(REPO, ".jax_cache")


def test_absent_platform_raises_and_never_answers_cpu():
    proc = _child(
        "import mxnet_tpu as mx\n"
        "print('BACKEND=' + mx.context.default_backend())\n"
        "print('CONTEXT=' + str(mx.current_context()))\n",
        {"JAX_PLATFORMS": "no_such_platform"})
    assert proc.returncode != 0
    assert "BACKEND=" not in proc.stdout and "CONTEXT=" not in proc.stdout
    assert "no_such_platform" in proc.stderr


def test_import_starts_no_child_process():
    out = _last_json(_child(
        "import json, sys\n"
        "started = []\n"
        "sys.addaudithook(lambda ev, a: started.append(ev) if ev in ("
        "'subprocess.Popen', 'os.fork', 'os.forkpty', 'os.posix_spawn', "
        "'os.exec', 'os.system') else None)\n"
        "import mxnet_tpu\n"
        "mxnet_tpu.np.ones((2, 2)).asnumpy()\n"
        "print(json.dumps(started))\n"))
    assert out == []


def test_spawned_children_are_pinned_by_jax_platforms_alone():
    from mxnet_tpu.context import spawn_cpu_pinned_env

    before = dict(os.environ)
    os.environ["JAX_PLATFORMS"] = "tpu"
    try:
        with spawn_cpu_pinned_env():
            inside = dict(os.environ)
        assert os.environ["JAX_PLATFORMS"] == "tpu"
    finally:
        os.environ["JAX_PLATFORMS"] = before["JAX_PLATFORMS"]
    assert inside["JAX_PLATFORMS"] == "cpu"
    assert set(inside) == set(before)      # no second variable


@pytest.fixture(scope="module")
def rehearsal():
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--cpu-rehearsal"], cwd=REPO, capture_output=True, text=True,
        timeout=900)


def test_chip_smoke_rehearsal_runs_both_phases(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-3000:]
    out = rehearsal.stdout
    assert "train: losses" in out and "train-padded: losses" in out
    assert "serve: compiles after warm-up: 0" in out
    assert "requests equal generate(use_cache=False)" in out
    assert "all phases passed" in out


def test_chip_smoke_rehearsal_never_says_ok(rehearsal):
    assert '"ok": true' not in rehearsal.stdout
    last = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] == "cpu"
    assert last["device"]["platform"] == "cpu"


def test_chip_smoke_fails_without_a_tpu():
    """The driver's call, in a sandbox: non-zero exit and no result line."""
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
