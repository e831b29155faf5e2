"""What the window's PUTs left on the drives, against the plain
reference: every shard file of a sample of objects, frame by frame
([32-byte HighwayHash-256][sub-block] per stripe block), data and
parity alike. A healthy GET never reads parity and trusts the stored
digests, so only this sees a wrong encode or a wrong digest.

Reads the drives as files, after the server has stopped. From the
program's xl.meta it takes only where it put things (the data
directory and which shard index a drive holds); every byte compared
comes from the reference."""

from __future__ import annotations

import json
import os
import random

from . import reference
from .traffic import sub_seed


def sample(objects: list[tuple[str, int, int]], n: int, seed: int
           ) -> list[tuple[str, int, int]]:
    """n of the (key, size, off) objects, drawn from the seed, one of
    the largest always among them."""
    objects = sorted(objects)
    if len(objects) <= n:
        return objects
    rng = random.Random(sub_seed(seed, "at_rest"))
    biggest = max(objects, key=lambda o: (o[1], o[0]))
    rest = [o for o in objects if o != biggest]
    return [biggest] + rng.sample(rest, n - 1)


def _stored(drive: str, bucket: str, key: str, part: int
            ) -> tuple[int, bytes] | None:
    """(1-based shard index, shard file) this drive holds, or None."""
    base = os.path.join(drive, bucket, key)
    try:
        with open(os.path.join(base, "xl.meta"), "rb") as f:
            ver = json.load(f)["versions"][0]
        with open(os.path.join(base, ver["dataDir"], f"part.{part}"),
                  "rb") as f:
            return int(ver["erasure"]["index"]), f.read()
    except (OSError, KeyError, IndexError, ValueError):
        return None


def check(drives: list[str], bucket: str,
          objects: list[tuple[str, int, int]], body_of, k: int, m: int,
          block: int, part_size: int = 0) -> dict:
    """Counts of what differs from the reference over `objects`.
    `body_of(size, off)` gives an object's bytes; `part_size` > 0 for
    objects written as multipart uploads of that part size."""
    out = {"objects_checked": 0, "shard_files_checked": 0,
           "frames_checked": 0, "shard_files_missing": 0,
           "shard_frames_differ": 0, "digest_frames_differ": 0}
    parts_of, blocks_of = [], []
    for key, size, off in objects:
        body = body_of(size, off)
        step = part_size or max(size, 1)
        for pn, lo in enumerate(range(0, size, step), start=1):
            parts_of.append((key, pn))
            blocks_of.append(reference.shard_blocks(
                body[lo:lo + step], k, m, block))
    digests_of = reference.digests_for(blocks_of)
    for (key, pn), blocks, digests in zip(parts_of, blocks_of, digests_of):
        want = reference.shard_files(blocks, digests)
        seen: set[int] = set()
        for d in drives:
            got = _stored(d, bucket, key, pn)
            if got is None or not 1 <= got[0] <= k + m or got[0] in seen:
                out["shard_files_missing"] += 1
                continue
            idx, data = got
            seen.add(idx)
            out["shard_files_checked"] += 1
            exp = want[idx - 1]
            out["frames_checked"] += len(blocks)
            if data == exp:
                continue
            if len(data) != len(exp):
                out["shard_frames_differ"] += len(blocks)
                continue
            pos = 0
            for arr in blocks:
                n = arr.shape[1]
                if data[pos:pos + 32] != exp[pos:pos + 32]:
                    out["digest_frames_differ"] += 1
                if data[pos + 32:pos + 32 + n] != exp[pos + 32:pos + 32 + n]:
                    out["shard_frames_differ"] += 1
                pos += 32 + n
        if pn == 1:
            out["objects_checked"] += 1
    return out
