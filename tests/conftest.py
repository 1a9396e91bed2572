"""Test harness: force a pure 8-device virtual CPU platform.

Multi-device-without-a-pod strategy (SURVEY.md §4): DP/TP/SP sharding
correctness is validated on a virtual CPU mesh
(``--xla_force_host_platform_device_count=8``). Nothing here needs a chip;
``chip_smoke.py`` is what runs on one.
"""

import functools
import os
import re
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# The suite runs with JAX's persistent compilation cache off. The entry
# points' main() would otherwise place it at one fixed directory in the
# checkout (utils/compile_cache.py), shared by every test and every child
# process: a slower suite at best, a flaky one at worst. Children inherit
# the variable; tests of the cache itself turn it on for themselves.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --- shared subprocess-spawn helpers ---------------------------------------
# Several suites (test_multiprocess, test_supervisor, test_serve_tp) spawn
# real Python subprocesses that must see a forced virtual CPU device
# count. The env recipe is identical everywhere; keep it in ONE
# place so "how do child processes get N devices" has a single answer.

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_script(name):
    """Import ``scripts/<name>.py`` by path (scripts/ is not a package).
    trace_report and load_gen are stdlib-only, so this stays cheap."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def activation_scatters(hlo_text, width):
    """``scatter`` instructions of an HLO text (``lowered.as_text(dialect="hlo")``)
    with an operand or result ``width`` wide in its last dimension."""
    return [ln for ln in hlo_text.splitlines()
            if re.search(r"\bscatter\(", ln) and re.search(r"\[[0-9,]*\b%d\]" % width, ln)]


def tail_after_loop(sigmoid_routed_ffn):
    """``moe.sigmoid_routed_ffn`` as a routed layer had it until PR 36: the
    layer's tail applied after the chunk loop, to the loop's whole value (a
    tail takes any token axes). What the tail inside the loop is held to."""
    def after(p, x, *a, tail=None, operands=(), **kw):
        y, stats = sigmoid_routed_ffn(p, x, *a, **kw)
        return (y if tail is None else tail(y, *(whole for _, whole in operands))), stats
    return after


def device_env(n, base=None):
    """Child-process env with ``n`` virtual CPU devices.

    Sets PYTHONPATH to the repo root (so the package imports from any cwd),
    forces the CPU backend, and forces the host-platform device count.
    """
    env = dict(os.environ if base is None else base)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={int(n)}"
    return env


def spawn_with_devices(argv, n, **popen_kw):
    """subprocess.Popen(argv) under device_env(n), output captured as text."""
    import subprocess

    kw = dict(stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    kw.update(popen_kw)
    return subprocess.Popen(argv, env=device_env(n), **kw)


# --- serial scheduling for thread-heavy drills ------------------------------
# A few serving tests run several live HTTP servers plus engine/router
# threads inside the test process and assert on stream timing. Under a
# loaded batch (xdist workers, a busy CI box) they flake purely from
# scheduler contention. The ``serial`` marker (pytest.ini) moves them to
# the END of the collection order — they run after the bulk of the suite
# has released its threads — and pins them all to one xdist group so a
# parallel runner never splits them across simultaneously-busy workers.

def pytest_collection_modifyitems(config, items):
    serial = [it for it in items if it.get_closest_marker("serial")]
    if not serial:
        return
    rest = [it for it in items if not it.get_closest_marker("serial")]
    for it in serial:
        it.add_marker(pytest.mark.xdist_group("serial"))
    items[:] = rest + serial


# --- both paths of the flash kernels on one test -----------------------------
# ops/flash_attention.py picks each kernel's path (operands resident in VMEM,
# or streamed through the grid) from the call's shapes, and at test sizes that
# is always the resident one. A test that takes ``flash_path`` runs once under
# each: every raw call inside it, forward or backward (the custom-vjp
# wrapper's, ring attention's), is forced through the internal ``_path``
# argument, and the tally has to show that the named path, and not the other,
# was traced, for the forward and for whichever backward kernels ran.

@pytest.fixture(params=("resident", "streamed"))
def flash_path(request, monkeypatch):
    from mlx_cuda_distributed_pretraining_tpu.ops import flash_attention as fa

    path = request.param
    other = "streamed" if path == "resident" else "resident"
    for entry in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        monkeypatch.setattr(fa, entry, functools.partial(getattr(fa, entry), _path=path))
    fa._cached_core.cache_clear()  # a core traced under the other path
    before = fa.plan_counts()
    yield path
    fa._cached_core.cache_clear()
    after = fa.plan_counts()
    assert after[path] > before[path], f"no {path} forward was traced"
    for key in (other, f"bwd_dq_{other}", f"bwd_dkv_{other}"):
        assert after[key] == before[key], f"a {key} kernel was traced"
    assert (after[f"bwd_dq_{path}"] - before[f"bwd_dq_{path}"]
            == after[f"bwd_dkv_{path}"] - before[f"bwd_dkv_{path}"])


# --- no mesh left behind by another file's Trainer ------------------------------
# A Trainer hands its mesh to parallel/context.set_mesh, where it outlives the Trainer; under a
# mesh of several devices the ops that choose a path from what they can see (ops/kda.py,
# ops/short_conv.py: GSPMD cannot partition their kernels) take their XLA form. A test that counts
# kernel calls runs under this fixture, so that what ran before it in its worker decides nothing.

@pytest.fixture
def no_mesh_left_behind(monkeypatch):
    from mlx_cuda_distributed_pretraining_tpu.parallel import context

    monkeypatch.setattr(context, "_BASE", [None])
