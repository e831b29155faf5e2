#!/usr/bin/env python3
"""The serving child with ONE fault planted in the program's timed path
(BENCH_FAULT), for test_faults.py: the harness drives a whole run over
it and has to come out `correct: false`.

    get     one byte of a GET body altered where it is produced, from
            the BENCH_FAULT_AFTER-th GET on (the warm-up's pass clean, so
            that the window's own comparison has to catch it)
    stale   a PUT over an existing key acknowledges and changes nothing
    shard   one byte of a shard sub-block altered as drive d2 writes it
    digest  one byte of a bitrot digest altered as drive d2 writes it
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "harness"))
import server_child  # noqa: E402

sys.path.insert(0, server_child.ROOT)


def plant(fault: str) -> None:
    from minio_tpu.erasure.engine import ErasureObjects, ObjectNotFound
    from minio_tpu.storage.xl import XLStorage

    if fault == "get":
        real = ErasureObjects.get_object_stream
        clean = [int(os.environ.get("BENCH_FAULT_AFTER", "0"))]

        def get_object_stream(self, *a, **kw):
            info, stream = real(self, *a, **kw)
            if clean[0] > 0:
                clean[0] -= 1
                return info, stream

            def altered():
                first = True
                for chunk in stream:
                    if first and len(chunk):
                        chunk = bytes([chunk[0] ^ 1]) + bytes(chunk[1:])
                        first = False
                    yield chunk
            return info, altered()
        ErasureObjects.get_object_stream = get_object_stream
    elif fault == "stale":
        real_put = ErasureObjects.put_object

        def put_object(self, bucket, object_name, data, **kw):
            if bucket == "bench":
                try:
                    info = self.get_object_info(bucket, object_name)
                except ObjectNotFound:
                    return real_put(self, bucket, object_name, data, **kw)
                from minio_tpu.utils import streams
                reader = streams.ensure_reader(data)
                while reader.read(1 << 20):
                    pass
                return info
            return real_put(self, bucket, object_name, data, **kw)
        ErasureObjects.put_object = put_object
    elif fault in ("shard", "digest"):
        real_append = XLStorage.append_file
        at = 40 if fault == "shard" else 0

        def append_file(self, volume, path, data):
            if self.root.endswith("/d2") and path.endswith("/part.1") \
                    and len(data) > 64:
                data = bytearray(data)
                data[at] ^= 1
                data = bytes(data)
            return real_append(self, volume, path, data)
        XLStorage.append_file = append_file
    else:
        raise SystemExit(f"unknown BENCH_FAULT {fault!r}")


if __name__ == "__main__":
    plant(os.environ["BENCH_FAULT"])
    sys.exit(server_child.main())
