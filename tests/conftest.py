"""Test harness: small world, real runtime.

The reference tests run against a real local Spark session with
``local[2]`` + 2 partitions — the minimal config where barrier
execution and a world_size-3 gloo group are actually exercised
(``tests/test_sparktorch.py:13-26``). The TPU-native analog is an
8-device CPU-backend XLA mesh via
``--xla_force_host_platform_device_count`` (SURVEY §4 implication),
so every collective and sharding path runs for real.

This must happen before any test initializes a JAX backend.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
    # The CPU thunk executor's concurrency-optimized scheduler runs
    # independent collectives of ONE launch concurrently, but the
    # in-process rendezvous keys every collective of an executable
    # with the same op_id — two overlapping same-shape collectives
    # mix rendezvous and flakily deadlock (or crash with a
    # 9th-of-8-participants check) on manual-collective-dense
    # programs like the 1F1B tick. Program-order scheduling removes
    # the hazard on the virtual-device rig; real TPU is unaffected.
    + " --xla_cpu_enable_concurrency_optimized_scheduler=false"
)

import jax

jax.config.update("jax_platforms", "cpu")
if os.environ.get("SPARKTORCH_TPU_TEST_FASTCOMPILE"):
    jax.config.update("jax_disable_most_optimizations", True)

# The persistent compilation cache is ARMED by default for the suite
# (a fresh per-session tmp dir), re-enabled after the restore <->
# collective SIGABRT was chased into the runtime (ROADMAP 4b):
# HISTORY (2026-08-03 bisect, kept because each clue was hard-won):
# with the cache armed, the suite aborted deterministically inside
# tests/test_checkpoint.py::test_streaming_trainer_checkpoint_resume
# whenever ANY earlier in-process orbax restore had run — even
# test_model_save_load -> streaming, where the predecessor only does
# load_model (restore, no training, no collectives); every test alone
# was green (cold cache), the save-only pair was green, the reverse
# order was green. So: orbax restore anywhere in the process, THEN
# cache-mediated collective compile/dispatch -> SIGABRT.
# ROOT CAUSE OF THE LINGERING CRASH (2026-08-04): the disarm hook in
# utils/checkpoint.py nulled jax_compilation_cache_dir, but on this
# jax that is NOT a disarm once any compile has happened —
# compilation_cache.is_cache_used LATCHES a module-global at the
# first compile and _get_cache keeps serving the initialized cache
# object, so the "disarmed" runtime kept using the cache and aborted.
# The hook now also calls compilation_cache.reset_cache() (drops the
# latch + cache object), after which the bisected pair and the full
# suite run green with the cache armed. A softer reset-but-keep-
# armed mode was tried and still aborts (see the hook's docstring) —
# after the first restore the process runs uncached, which is the
# safe trade. Everything BEFORE the first restore (and any session
# without one) gets persistent-cache speed.
# Knobs:
# - SPARKTORCH_TPU_TEST_CACHE=0|off  -> cache disarmed (old default)
# - SPARKTORCH_TPU_TEST_CACHE=<dir> -> that dir (persistent across
#   sessions; safe — pre-restore deserialized collective execution
#   is green, reproduced in tests/test_checkpoint.py's cache tests)
# - unset -> fresh tmp dir for this session
# - SPARKTORCH_TPU_ISOLATE_STREAMING=1 -> the streaming-trainer
#   checkpoint test re-runs itself in a SUBPROCESS (fresh process =
#   no prior restore = cache armed all the way through it); the
#   escape hatch for rigs where the in-process disarm is not enough.
# - JAX_COMPILATION_CACHE_DIR set -> jax already uses that directory;
#   the suite sets none of its own
_CACHE_DIR = os.environ.get("SPARKTORCH_TPU_TEST_CACHE")
if _CACHE_DIR in ("0", "off") or os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _CACHE_DIR = None
elif not _CACHE_DIR:
    import atexit
    import shutil
    import tempfile

    _CACHE_DIR = tempfile.mkdtemp(prefix="sparktorch_tpu_xla_cache_")
    # Session-scoped: nothing re-reads a fresh dir after the session,
    # so leaving it behind would be a pure disk leak on a TDD loop.
    atexit.register(shutil.rmtree, _CACHE_DIR, True)
if _CACHE_DIR:
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# The mesh="auto" builder's own persistent-cache arming
# (SPARKTORCH_TPU_XLA_CACHE) is OFF by default for the suite: the
# session cache above already covers the suite, and a test must never
# write into the checkout's cache directory. Cache tests opt in explicitly.
os.environ.setdefault("SPARKTORCH_TPU_XLA_CACHE", "0")

# The tune-result cache is OFF by default for the suite: tests must
# be hermetic (no reads of — or writes to — the user's ~/.cache, and
# no cross-run coupling where a stale entry from an older code
# version decides a deterministic assertion). The cache's own tests
# point SPARKTORCH_TPU_TUNE_CACHE at a tmp dir explicitly; an
# externally-set value is respected.
os.environ.setdefault("SPARKTORCH_TPU_TUNE_CACHE", "0")

import numpy as np
import pytest

from sparktorch_tpu.ml.dataset import LocalDataFrame


N_DEVICES = 8


@pytest.fixture(scope="session", autouse=True)
def _assert_world():
    assert len(jax.devices()) == N_DEVICES, (
        "tests expect an 8-device CPU XLA world; got "
        f"{len(jax.devices())} ({jax.default_backend()})"
    )


@pytest.fixture(scope="session")
def data() -> LocalDataFrame:
    """Two 200-row Gaussian blobs (mu=0 vs mu=2, 10-dim) as
    (label, features) rows — the reference's fixture dataset
    (tests/test_sparktorch.py:21-26)."""
    rng = np.random.default_rng(42)
    x0 = rng.normal(0.0, 1.0, size=(200, 10)).astype(np.float32)
    x1 = rng.normal(2.0, 1.0, size=(200, 10)).astype(np.float32)
    x = np.concatenate([x0, x1])
    y = np.concatenate([np.zeros(200), np.ones(200)]).astype(np.float32)
    perm = rng.permutation(400)
    return LocalDataFrame({"label": y[perm], "features": list(x[perm])}).repartition(2)


@pytest.fixture(scope="session")
def mesh():
    from sparktorch_tpu.parallel.mesh import local_mesh

    return local_mesh()
