#!/usr/bin/env python3
"""The serving child: `python -m minio_tpu <argv>` in all but name.

Calls `minio_tpu.__main__.main(argv)` on the main thread (same boot
lines, same signal handling). A side thread reads commands, one per
line, from stdin and answers on stdout as `bench-ctl: <json>`:

    trace_start <dir>   jax.profiler.start_trace(<dir>)
    trace_stop          jax.profiler.stop_trace()
    mem                 peak_bytes_in_use of every local device

Only the process that holds the chip can trace it or ask it for its
memory, and the program has no such entry yet. Nothing happens until a
command arrives; with stdin at EOF the thread ends. The parent sends
the next command of a kind only after the last one's answer.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _answer(obj: dict) -> None:
    sys.stdout.write("bench-ctl: " + json.dumps(obj) + "\n")
    sys.stdout.flush()


def _command(line: str) -> dict:
    cmd, _, arg = line.strip().partition(" ")
    import jax
    if cmd == "trace_start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t0 = time.monotonic()
        jax.profiler.start_trace(arg, profiler_options=opts)
        return {"cmd": cmd, "t_call": t0, "t_done": time.monotonic()}
    if cmd == "trace_stop":
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        return {"cmd": cmd, "t_call": t0, "t_done": time.monotonic()}
    if cmd == "mem":
        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peaks.append(stats.get("peak_bytes_in_use"))
        return {"cmd": cmd, "peak_bytes_in_use": peaks}
    return {"cmd": cmd, "error": "unknown command"}


def _run(line: str) -> None:
    try:
        _answer(_command(line))
    except Exception as exc:  # noqa: BLE001 - the parent decides
        _answer({"cmd": line.split()[0],
                 "error": f"{type(exc).__name__}: {exc}"})


def _control() -> None:
    # A thread per command: stop_trace can work for minutes, and `mem`
    # has to be answered meanwhile.
    for line in sys.stdin:
        if line.strip():
            threading.Thread(target=_run, args=(line,), daemon=True).start()


def main() -> int:
    sys.path.insert(0, ROOT)
    from minio_tpu.__main__ import main as serve
    threading.Thread(target=_control, name="bench-ctl",
                     daemon=True).start()
    return serve(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
