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
    heal_noop   a heal classifies, writes nothing and reports every
                missing drive healed (a step that returns its state
                unchanged and says it did the work)
    heal_shard  one byte of a REBUILT shard's sub-block altered as the
                heal writes it (PUTs write sound shards)
    heal_raced  the new-disk monitor's tick falls between the wipe and
                the admin sweep's first step: its own sweep of the drive
                runs, here to its end, before the admin's goes on
    delete_noop    a DELETE answers 204 and removes nothing
    delete_orphan  a DELETE removes each drive's xl.meta and leaves the
                   object's data directory behind
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
    elif fault in ("heal_noop", "heal_shard"):
        from minio_tpu.erasure.heal import Healer
        real_heal = Healer._heal_object_locked
        healing = []
        real_append = XLStorage.append_file

        def _heal_object_locked(self, bucket, object_name, dry_run=False):
            if fault == "heal_shard":
                healing.append(1)
                try:
                    return real_heal(self, bucket, object_name, dry_run)
                finally:
                    healing.pop()
            res = real_heal(self, bucket, object_name, True)
            if not dry_run and res.missing_disks and not res.dangling:
                res.healed_disks = list(res.missing_disks)
                res.after_ok = res.total_disks
            return res

        def append_file(self, volume, path, data):
            if healing and path.endswith("/part.1") and len(data) > 64:
                data = bytearray(data)
                data[40] ^= 1
                data = bytes(data)
            return real_append(self, volume, path, data)
        Healer._heal_object_locked = _heal_object_locked
        if fault == "heal_shard":
            XLStorage.append_file = append_file
    elif fault == "heal_raced":
        from minio_tpu.erasure.heal import Healer
        real_bucket = Healer.heal_bucket
        ticked = []

        def heal_bucket(self, bucket):
            gone = any(hasattr(d, "root") and not os.path.isdir(
                os.path.join(d.root, bucket)) for d in self.engine.disks)
            if gone and not ticked:
                ticked.append(1)
                self.engine.new_disk_monitor.tick()
            return real_bucket(self, bucket)
        Healer.heal_bucket = heal_bucket
    elif fault == "delete_noop":
        from minio_tpu.erasure.engine import ObjectInfo
        real_delete = ErasureObjects.delete_object

        def delete_object(self, bucket, object_name, *a, **kw):
            if bucket == "bench":
                return ObjectInfo(bucket=bucket, name=object_name)
            return real_delete(self, bucket, object_name, *a, **kw)
        ErasureObjects.delete_object = delete_object
    elif fault == "delete_orphan":
        real_version = XLStorage.delete_version

        def delete_version(self, volume, path, fi):
            if volume == "bench":
                os.remove(os.path.join(self._file_path(volume, path),
                                       "xl.meta"))
                return None
            return real_version(self, volume, path, fi)
        XLStorage.delete_version = delete_version
    else:
        raise SystemExit(f"unknown BENCH_FAULT {fault!r}")


if __name__ == "__main__":
    plant(os.environ["BENCH_FAULT"])
    sys.exit(server_child.main())
