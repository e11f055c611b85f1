"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §4 build note)
so DP/FSDP/TP/SP paths are testable with no TPU.

``JAX_PLATFORMS`` and ``XLA_FLAGS`` are set here, before jax is imported:
the platform list is read at import and XLA_FLAGS at backend init (first
device access). ``jax.config.update`` below pins the platform as well, for
a process in which something imported jax before this file ran.
Subprocesses spawned by tests inherit the env vars and stay hermetic too.
"""

import os
import subprocess
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# Virtual devices serialize on few cores: a collective legitimately waits
# while its peers' compute grinds through the same core(s), and XLA's
# in-process stuck detector would abort the run (seen on the flagship-8B
# test: minutes of single-core RNG/GEMM between peers). Shared with the
# subprocess harness in test_fault_tolerance.py.
COLLECTIVE_TIMEOUT_FLAGS = (
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=3600"
    " --xla_cpu_collective_call_terminate_timeout_seconds=7200")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_collective_call_warn_stuck" not in flags:
    flags += " " + COLLECTIVE_TIMEOUT_FLAGS
os.environ["XLA_FLAGS"] = flags

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")

# Numerics tests compare against fp64/fp32 oracles; JAX's *default* matmul
# precision truncates to bf16-class even on CPU in this build.
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second CPU tests (multi-round speculative streams, "
        "big layout matrices); tier-1 runs -m 'not slow'")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection scenarios (tests/test_chaos.py); the heavy "
        "end-to-end ones are also slow-marked")


_MP_PROBE_WORKER = """
import os, sys
os.environ.pop('XLA_FLAGS', None)
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
jax.distributed.initialize(sys.argv[2], num_processes=2,
                           process_id=int(sys.argv[1]))
from jax.experimental import multihost_utils
multihost_utils.sync_global_devices('probe')  # cross-process XLA collective
print('MP_OK', flush=True)
os._exit(0)  # skip jax.distributed.shutdown: its barrier can stall atexit
"""


def _once_per_jaxlib(name: str, probe) -> bool:
    """A capability of this jaxlib's CPU backend, probed once and kept by
    jaxlib version under ``tempfile.gettempdir()`` (the run's own TMPDIR
    where it has one, so two checkouts never read each other's verdict):
    later sessions and xdist workers read it instead of paying the probe's
    subprocesses again. A probe that cannot decide raises, and nothing is
    kept."""
    import jaxlib

    cache = os.path.join(tempfile.gettempdir(),
                         f"_ftl_{name}_probe_{jaxlib.__version__}")
    try:
        with open(cache) as f:
            return f.read() == "1"
    except OSError:
        pass
    ok = probe()
    try:
        with open(cache, "w") as f:
            f.write("1" if ok else "0")
    except OSError:
        pass
    return ok


def _probe_multiprocess_cpu_jit() -> bool:
    """The multi-host pod tests run real 2-process jax.distributed clusters
    on the CPU backend. Some jaxlibs cannot execute multiprocess XLA
    computations on CPU at all — one process raises 'Multiprocess
    computations aren't implemented on the CPU backend' while its peer
    WEDGES inside the collective (and then the shutdown barrier burns its
    full 5-minute timeout). Each pod test would then eat its entire
    subprocess timeout x3 retries, starving the rest of the suite. Probe
    the exact failing op (a cross-process sync) in throwaway subprocesses
    (once per jaxlib version: ``_once_per_jaxlib``) and let the pod tests
    skip when it can't run."""
    import socket
    import time

    with socket.socket() as s:
        s.bind(("localhost", 0))
        coord = f"localhost:{s.getsockname()[1]}"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _MP_PROBE_WORKER, str(i), coord],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
        for i in range(2)]
    deadline = time.monotonic() + 90
    ok = True
    for p in procs:
        try:
            rc = p.wait(timeout=max(0.1, deadline - time.monotonic()))
            ok = ok and rc == 0
        except subprocess.TimeoutExpired:
            ok = False
    for p in procs:
        if p.poll() is None:
            p.kill()  # a wedged collective ignores SIGTERM
            p.wait()
    return ok


@pytest.fixture(scope="session")
def multiprocess_cpu_jit():
    """Pod tests that jit XLA computations across a real 2-process CPU
    cluster declare this fixture; it skips them on jaxlibs whose CPU
    backend cannot run multiprocess programs (see the probe above)."""
    if not _once_per_jaxlib("multiprocess_cpu",
                            _probe_multiprocess_cpu_jit):
        pytest.skip("this jaxlib's CPU backend cannot execute multiprocess "
                    "XLA computations (capability probe failed)")


_DOT_PROBE = """
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax, jax.numpy as jnp
jax.config.update('jax_platforms', 'cpu')
sys.path.insert(0, sys.argv[1])
from _tiny import REFUSED_DOT, REFUSED_DOT_SHAPES
p, v = (jnp.ones(s, jnp.bfloat16) for s in REFUSED_DOT_SHAPES)
jnp.einsum(REFUSED_DOT, p, v,
           preferred_element_type=jnp.float32).block_until_ready()
"""


def _probe_cpu_bf16_serving_dot() -> bool:
    """Whether this XLA:CPU executes the bf16 x bf16 -> float32 einsum of
    ``ops/attention.py`` ``cached_attention`` (``_tiny.REFUSED_DOT``), in a
    throwaway subprocess. In-process tests do not need it (they build
    float32 engines through ``_tiny.tiny_cfg``); the serve CLI has no model
    dtype input and serves every checkpoint at the preset's bf16.

    False only when the subprocess's stderr names the refusal; a timeout or
    any other failure (an import error, an OOM) raises with that text, so
    it is neither kept as a verdict nor read as one."""
    from _tiny import REFUSED_DOT_ERRORS

    try:
        out = subprocess.run(
            [sys.executable, "-c", _DOT_PROBE, os.path.dirname(__file__)],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            timeout=120)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(f"serving-dot probe undecided: {e}") from None
    if out.returncode == 0:
        return True
    if any(text in out.stderr for text in REFUSED_DOT_ERRORS):
        return False
    raise RuntimeError(f"serving-dot probe undecided (exit code "
                       f"{out.returncode}): {out.stderr[-2000:]}")


@pytest.fixture(scope="session")
def cpu_bf16_serving_dot():
    """Tests that drive the serve CLI in a subprocess declare this fixture;
    it skips them where XLA:CPU refuses the decode program's dot, and fails
    them where the probe could not tell."""
    try:
        runs = _once_per_jaxlib("cpu_bf16_dot", _probe_cpu_bf16_serving_dot)
    except RuntimeError as e:
        pytest.fail(str(e))
    if not runs:
        from _tiny import REFUSED_DOT

        pytest.skip(
            f"this XLA:CPU refuses the serving decode dot ({REFUSED_DOT!r} "
            "at S = 1, bf16 x bf16 -> float32) and the serve CLI serves "
            "every checkpoint at bf16; runs on the chip in chip_smoke.py's "
            "serve phase")


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    assert jax.device_count() >= 8
    return jax.devices()[:8]


@pytest.fixture()
def tiny_parquet(tmp_path):
    """Synthetic 'text'-column parquet file (the reference's data contract:
    utils.py:118 'a parquet file containing a text column with documents')."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(0)
    docs = []
    words = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot", "golf",
             "hotel", "india", "juliet"]
    for i in range(64):
        n = int(rng.integers(5, 120))
        docs.append(" ".join(rng.choice(words, size=n).tolist()))
    path = tmp_path / "train_data.parquet"
    pq.write_table(pa.table({"text": docs}), path)
    return str(path)
