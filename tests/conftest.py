"""Test config: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual CPU mesh (the driver separately dry-run-compiles the
multi-chip path via __graft_entry__.dryrun_multichip). Must run before any
jax import, hence top of conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# JAX reads JAX_PLATFORMS itself. Imported HERE so that its internals
# stay out of the lock tracer installed below.
import jax  # noqa: E402,F401


# Runtime lock-order sanitizer: the whole tier-1 suite runs with traced
# locks (utils/locktrace.py) so every test doubles as a deadlock-
# potential probe. Installed HERE, before any minio_tpu module import,
# so module-level locks are traced too; jax's internals (imported
# above) stay untraced by construction order. The session-end hook
# below turns any recorded lock-order cycle into a suite failure.
os.environ.setdefault("MTPU_LOCKTRACE", "1")

from minio_tpu.utils import locktrace  # noqa: E402

locktrace.maybe_install()


def pytest_sessionfinish(session, exitstatus):
    if not locktrace.installed():
        return
    cycles = locktrace.cycles()
    rep = locktrace.report()
    if rep:
        print("\n" + rep)
    if cycles:
        # A lock-order cycle is a potential deadlock even when this
        # run's schedule did not trip it — fail the session.
        session.exitstatus = max(int(exitstatus), 1)


# Optional-dep gate: SSE/TLS tests run only where the cryptography
# package exists (the server itself boots without it and serves plain
# objects — crypto/sse.py gates the import).
import importlib.util  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

needs_crypto = pytest.mark.skipif(
    importlib.util.find_spec("cryptography") is None,
    reason="needs the optional cryptography package")


@pytest.fixture(autouse=True)
def _join_codec_probe():
    """The first in-process server starts the process-wide
    `codec-autotune-probe` thread, whose ladder logs to stderr for ~20 s.
    A line written between two tests, while pytest's capture is
    suspended, lands in the middle of a line of dots and the driver's
    pass count then under-reads. Joining the thread in the teardown of
    the test that started it keeps every line inside that test's
    capture."""
    yield
    autotune = sys.modules.get("minio_tpu.ops.autotune")
    t = autotune and autotune.AUTOTUNE._probe_thread
    if t is not None and t.is_alive():
        t.join(120)


@pytest.fixture(autouse=True, scope="session")
def _probe_thread_logs_to_the_ring_only():
    """The join above cannot cover the moment between the SETUP and the
    CALL phase of the test whose fixture started the thread: capture is
    suspended there too, and the ladder's first line (a few ms after
    the start) landed in the dots once in PR 26's runs (1124 passed,
    1105 dots counted). Lines from that thread go to the log ring
    only, which is where the tests read them."""
    import threading
    import time

    from minio_tpu.logger import logger as lg
    emit = lg.Logger._emit

    def ring_only(self, level, message, source="", **fields):
        if threading.current_thread().name != "codec-autotune-probe":
            return emit(self, level, message, source, **fields)
        self.ring.add(lg.LogEntry(level=level, time=time.time(),
                                  message=message, source=source,
                                  fields=dict(fields)))

    lg.Logger._emit = ring_only
    yield
    lg.Logger._emit = emit
