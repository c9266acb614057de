"""PR 21 (first run on a directly attached chip): the compile-cache
resolver, the native extension's content-hash staleness, and
``chip_smoke.py``'s gate and rehearsal. (``dryrun_multichip`` leaving
the platform alone is asserted in tests/test_graft_entry.py.)"""

import json
import os
import subprocess
import sys

import pytest

from tensorframes_tpu import config as cfgmod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "TFTPU_COMPILE_CACHE")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


# -- compile-cache resolver ---------------------------------------------------

def test_resolver_precedence(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TFTPU_COMPILE_CACHE", raising=False)
    # neither set: library import stays cache-off; entry points get the
    # checkout's fixed, git-ignored directory
    assert cfgmod.resolve_compile_cache_dir() == ""
    fixed = cfgmod.resolve_compile_cache_dir(entry_point=True)
    assert fixed == cfgmod.CHECKOUT_CACHE_DIR == os.path.join(
        REPO, ".tftpu_cache")
    assert fixed == cfgmod.resolve_compile_cache_dir(entry_point=True)
    monkeypatch.setenv("TFTPU_COMPILE_CACHE", "/x/tftpu")
    assert cfgmod.resolve_compile_cache_dir() == "/x/tftpu"
    assert cfgmod.resolve_compile_cache_dir(entry_point=True) == "/x/tftpu"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/jax")
    assert cfgmod.resolve_compile_cache_dir() == "/x/jax"
    assert cfgmod.resolve_compile_cache_dir(entry_point=True) == "/x/jax"


def test_checkout_cache_dir_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".tftpu_cache/" in f.read().split()


_PROBE = r"""
import json, sys
import jax
calls = []
real = jax.config.update
def spy(name, value):
    calls.append([name, str(value)])
    return real(name, value)
jax.config.update = spy
import tensorframes_tpu as tfs
from tensorframes_tpu.config import use_compile_cache
entry = use_compile_cache(entry_point=True)
print(json.dumps({
    "calls": [c for c in calls if "compilation_cache" in c[0]],
    "cfg": tfs.configure().compilation_cache_dir,
    "entry": entry,
    "jax": jax.config.jax_compilation_cache_dir,
}))
"""


def _probe(env):
    r = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_jax_cache_dir_from_outside_is_never_overridden(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the package's layers live under
    it and NO code calls jax.config.update for the cache."""
    d = str(tmp_path / "outside")
    got = _probe(_clean_env(JAX_COMPILATION_CACHE_DIR=d,
                            TFTPU_COMPILE_CACHE=str(tmp_path / "loser")))
    assert got["calls"] == []
    assert got["cfg"] == got["entry"] == got["jax"] == d


def test_tftpu_cache_var_and_entry_point_default(tmp_path):
    d = str(tmp_path / "tftpu")
    got = _probe(_clean_env(TFTPU_COMPILE_CACHE=d))
    assert got["cfg"] == got["entry"] == got["jax"] == d
    got = _probe(_clean_env())
    # plain import left the cache off; the entry point then placed it
    # at the fixed path — in code, because nothing outside did
    assert got["entry"] == got["jax"] == cfgmod.CHECKOUT_CACHE_DIR
    assert got["calls"] == [
        ["jax_compilation_cache_dir", cfgmod.CHECKOUT_CACHE_DIR]]


def test_serving_fleet_defaults_to_the_resolver(monkeypatch, tmp_path):
    from tensorframes_tpu.serving import ServingFleet

    monkeypatch.delenv("TFTPU_COMPILE_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    fleet = ServingFleet(["true"], 1, rendezvous_dir=str(tmp_path / "rdv"))
    assert fleet.compile_cache == str(tmp_path / "cc")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fleet = ServingFleet(["true"], 1, rendezvous_dir=str(tmp_path / "rdv"))
    assert fleet.compile_cache == cfgmod.CHECKOUT_CACHE_DIR
    assert str(tmp_path) not in fleet.compile_cache


# -- native extension: staleness by content hash ------------------------------

def test_native_rebuilds_on_hash_mismatch_not_on_mtime(monkeypatch):
    from tensorframes_tpu import native

    if not native.available():
        pytest.skip("native extension unavailable")
    builds = []
    monkeypatch.setattr(native, "_build", lambda: builds.append(1) or True)

    def reload_status():
        monkeypatch.setattr(native, "_load_attempted", False)
        return native.status()

    # a newer source mtime alone (what a copy of the tree does) is not
    # staleness
    src = native._source_path()
    st = os.stat(src)
    try:
        os.utime(src, (st.st_atime + 10_000, st.st_mtime + 10_000))
        assert native._so_is_current()
        assert reload_status() == "loaded" and builds == []
    finally:
        os.utime(src, (st.st_atime, st.st_mtime))
    # a recorded hash that does not match the source is
    with open(native._hash_path()) as f:
        recorded = f.read()
    try:
        with open(native._hash_path(), "w") as f:
            f.write("0" * 64 + "\n")
        assert not native._so_is_current()
        assert reload_status() == "built" and builds == [1]
    finally:
        with open(native._hash_path(), "w") as f:
            f.write(recorded)
    assert native._so_is_current()


# -- chip_smoke.py --------------------------------------------------------------

def test_chip_smoke_refuses_cpu_before_any_leg():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_clean_env(), capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert "leg " not in r.stdout and '"ok"' not in r.stdout


def test_chip_smoke_parent_never_imports_jax():
    code = (
        "import sys, runpy; sys.argv=['chip_smoke.py', '--help']\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "except SystemExit:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-1000:]


def _assert_result_contract(result):
    assert set(result) == {"ok", "device"} and type(result["ok"]) is bool
    dev = result["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert type(dev["platform"]) is str and type(dev["kind"]) is str
    assert type(dev["count"]) is int


def test_chip_smoke_result_line_is_exactly_the_contract():
    """What the driver parses: the last line holds ``ok`` and ``device``
    and no other key (the per-leg summary is the line before it)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.result_line(True, {"platform": "tpu", "kind": "TPU v5 lite",
                                  "count": 1, "extra": "dropped"})
    assert "\n" not in line
    result = json.loads(line)
    _assert_result_contract(result)
    assert result == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.slow
def test_chip_smoke_rehearsal_runs_green(tmp_path):
    """Two passes at tiny sizes on the CPU; the second is served from
    the cache the first one filled."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearsal"],
        env=_clean_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc")),
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert all(ln.startswith("REHEARSAL") for ln in lines)
    # the result line: exactly the contract's keys, nothing beside them
    result = json.loads(lines[-1].removeprefix("REHEARSAL "))
    assert result["ok"] is True
    _assert_result_contract(result)
    prefix = "REHEARSAL summary: "
    assert lines[-2].startswith(prefix)
    summary = json.loads(lines[-2][len(prefix):])
    assert summary["ok"] is True and summary["claim"] is None
    assert set(summary["legs"].values()) == {"ok"}
    warm = summary["passes"][1]["cache"]
    assert warm["store_hits"] > 0 and warm["executor_compiles"] == 0
    # every cache file landed under the directory named from outside
    assert os.path.isdir(tmp_path / "cc" / "aot")
