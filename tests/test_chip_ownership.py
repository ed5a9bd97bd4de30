"""One process owns the accelerator, and nothing falls back in silence
(ISSUE 21).  Tier-1 runs on the CPU: what is checked here is the
selection logic, the refusals and the exit codes; the chip itself is
checked by `python chip_smoke.py` on the TPU machine."""

import json
import os
import subprocess
import sys

import jax
import pytest

from seaweedfs_tpu import native
from seaweedfs_tpu.storage.erasure_coding import ec_context
from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_process_that_owns_nothing_gets_the_host_engine(monkeypatch):
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_BACKEND", raising=False)
    assert ec_context._owner is None   # pytest never claimed the chip
    assert ECContext().backend in ("native", "cpu")
    # explicit requests stay explicit
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_BACKEND", "jax")
    assert ECContext().backend == "jax"
    assert ECContext(backend="cpu").backend == "cpu"


def test_owner_on_a_tpu_takes_the_probes_choice_and_a_failed_probe_raises(
        monkeypatch):
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_BACKEND", raising=False)
    monkeypatch.setattr(ec_context, "_cached_default", None)
    monkeypatch.setattr(ec_context, "_owner", {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})

    def broken():
        raise RuntimeError("h2d probe failed")
    monkeypatch.setattr(ec_context, "_measure_h2d_gbps", broken)
    with pytest.raises(RuntimeError, match="h2d probe failed"):
        ec_context.default_backend()
    monkeypatch.setattr(ec_context, "_measure_h2d_gbps", lambda: 1e9)
    assert ec_context.default_backend() == "jax"
    # an owner whose JAX was ASKED onto the cpu (tier-1) keeps the
    # host engine
    monkeypatch.setattr(ec_context, "_owner", {
        "platform": "cpu", "kind": "cpu", "count": 8})
    monkeypatch.setattr(ec_context, "_cached_default", None)
    assert ec_context.default_backend() in ("native", "cpu")


def test_device_codec_refuses_a_jax_that_fell_back_to_cpu():
    """JAX_PLATFORMS=cpu (conftest) is what makes JAX-on-CPU legal; a
    JAX that merely found no chip must not encode in silence."""
    assert ECContext(backend="jax").create_codec() is not None
    assert ec_context.where("jax")["platform"] == "cpu"
    asked = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(ec_context.DeviceUnavailable,
                           match="no accelerator"):
            ECContext(backend="jax").create_codec()
        # the host codecs are not the device's business
        assert ECContext(backend="cpu").create_codec() is not None
    finally:
        jax.config.update("jax_platforms", asked)


def test_compile_cache_dir_is_placed_from_outside_or_fixed(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert ec_context.compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert ec_context.compile_cache_dir() == \
        os.path.join(REPO, ".jax_cache")


def test_owner_caches_every_compile_where_the_env_says(tmp_path):
    """own_device() in a fresh process: the cache lands in
    JAX_COMPILATION_CACHE_DIR and nowhere else, sub-second compiles
    included, and a second process compiles nothing."""
    cache = tmp_path / "cache"
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from seaweedfs_tpu.storage.erasure_coding import ec_context\n"
        "dev = ec_context.own_device()\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
        "print(json.dumps({'dev': dev,\n"
        "    'dir': jax.config.jax_compilation_cache_dir,\n"
        "    'ledger': ec_context.compile_ledger()}))\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    fixed = os.path.join(REPO, ".jax_cache")

    def fixed_dir():
        return sorted(os.listdir(fixed)) if os.path.isdir(fixed) else None
    before = fixed_dir()
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=str(tmp_path), capture_output=True,
                           text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        runs.append(json.loads(p.stdout.splitlines()[-1]))
    cold, warm = runs
    assert cold["dev"]["platform"] == "cpu"
    assert cold["dir"] == str(cache)
    assert cold["ledger"]["compiled"] >= 1
    assert os.listdir(cache)
    assert fixed_dir() == before, "cache also written to the fixed dir"
    assert warm["ledger"]["compiled"] == 0 and \
        warm["ledger"]["cacheHits"] == warm["ledger"]["requests"] >= 1


def test_chip_smoke_fails_fast_and_names_the_missing_chip():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=30)
    assert p.returncode != 0
    assert "no chip" in p.stderr
    assert '"ok"' not in p.stdout


def test_native_artefact_is_keyed_on_content_and_host_cpu(tmp_path):
    """A stale `.so` — or one carried over from another machine, whose
    `-march=native` code may not run here — is rebuilt, never loaded:
    the key beside the artefact names source, flags and this host's
    CPU flags."""
    src = tmp_path / "t.cc"
    out = tmp_path / "_build" / "libt.so"
    src.write_text('extern "C" int f() { return 1; }\n')
    if native._build_if_stale(str(src), str(out)) is None:
        pytest.skip("no native toolchain")
    key = out.with_name("libt.so.key")
    built = out.stat().st_mtime_ns

    assert native._build_if_stale(str(src), str(out)) == str(out)
    assert out.stat().st_mtime_ns == built, "an intact artefact rebuilt"

    good = key.read_text()
    key.write_text("0" * 64)          # built elsewhere / for another CPU
    assert native._build_if_stale(str(src), str(out)) == str(out)
    assert key.read_text() == good
    assert out.stat().st_mtime_ns > built

    rebuilt = out.stat().st_mtime_ns
    src.write_text('extern "C" int f() { return 2; }\n')
    # same mtime as the artefact or older: only the content hash sees it
    os.utime(src, ns=(built, built))
    assert native._build_if_stale(str(src), str(out)) == str(out)
    assert out.stat().st_mtime_ns > rebuilt
    assert key.read_text() != good
