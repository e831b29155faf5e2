"""What the window's PUTs (or, where it writes nothing, the preload's)
left on the drives, against the plain reference: every shard file of a
sample of objects, frame by frame ([32-byte HighwayHash-256][sub-block]
per stripe block), data and parity alike. A healthy GET never reads
parity and trusts the stored digests, so only this sees a wrong encode
or a wrong digest.

Reads the drives as files, after the server has stopped. From the
program's xl.meta it takes only where it put things (the data
directory and which shard index a drive holds); every byte compared
comes from the reference.

Drives come in `sets` erasure sets of equal size, in launch order; an
object lives in one of them, and a copy of it in another is a copy
that should not be (counted as missing, as a hole in its own set is).
On a drive the traffic mix's fault took out (`lost`, positions in
`drives`) a copy is expected absent: one found there is
`lost_copies_present`, and the drive is left out of
`shard_files_missing`.

A key whose last acknowledged write was a DELETE has to be gone from
every drive: `deleted_present` counts those of which some drive still
holds an xl.meta or a data directory (the S3 contract on an unversioned
bucket; the reference's DeleteObject, cmd/erasure-object.go)."""

from __future__ import annotations

import json
import os
import random

from . import reference
from .traffic import sub_seed


def sample(objects: list[tuple[str, int, int]], n: int, seed: int,
           stratum=None) -> list[tuple[str, int, int]]:
    """n of the (key, size, off) objects, drawn from the seed, one of
    the largest always among them. With `stratum` (key -> a class) the
    draw is made class by class instead, each class's share of n its
    share of the objects and never under one."""
    objects = sorted(objects)
    if len(objects) <= n:
        return objects
    rng = random.Random(sub_seed(seed, "at_rest"))
    if stratum is not None:
        classes: dict = {}
        for o in objects:
            classes.setdefault(stratum(o[0]), []).append(o)
        take = {c: max(1, round(n * len(v) / len(objects)))
                for c, v in classes.items()}
        biggest = max(classes, key=lambda c: len(classes[c]))
        take[biggest] += n - sum(take.values())
        return sorted(o for c in sorted(classes)
                      for o in rng.sample(classes[c], take[c]))
    biggest = max(objects, key=lambda o: (o[1], o[0]))
    rest = [o for o in objects if o != biggest]
    return [biggest] + rng.sample(rest, n - 1)


DELETED_SAMPLE = 48  # deleted keys checked gone in a run


def sample_keys(keys, seed: int) -> list[str]:
    """DELETED_SAMPLE of the keys, drawn from the seed; all of them if
    there are fewer."""
    keys = sorted(keys)
    if len(keys) <= DELETED_SAMPLE:
        return keys
    return sorted(random.Random(sub_seed(seed, "deleted")).sample(
        keys, DELETED_SAMPLE))


def deleted_present(drives: list[str], bucket: str, keys: list[str]) -> int:
    """How many of the keys some drive still holds an xl.meta or a data
    directory of (each was acknowledged as deleted)."""
    present = 0
    for key in keys:
        for d in drives:
            base = os.path.join(d, bucket, key)
            try:
                names = os.listdir(base)
            except OSError:
                continue
            if any(n == "xl.meta" or os.path.isdir(os.path.join(base, n))
                   for n in names):
                present += 1
                break
    return present


def _placed(drive: str, bucket: str, key: str) -> tuple[int, str] | None:
    """(1-based shard index, data directory) of the newest version in
    this drive's xl.meta of the key, or None."""
    try:
        with open(os.path.join(drive, bucket, key, "xl.meta"), "rb") as f:
            ver = json.load(f)["versions"][0]
        return int(ver["erasure"]["index"]), str(ver["dataDir"])
    except (OSError, KeyError, IndexError, ValueError, TypeError):
        return None


def _stored(drive: str, bucket: str, key: str, part: int
            ) -> tuple[int, bytes] | None:
    """(1-based shard index, shard file) this drive holds, or None."""
    placed = _placed(drive, bucket, key)
    if placed is None:
        return None
    try:
        with open(os.path.join(drive, bucket, key, placed[1],
                               f"part.{part}"), "rb") as f:
            return placed[0], f.read()
    except OSError:
        return None


def lost_data_shards(drives: list[str], bucket: str, key: str, k: int,
                     lost: frozenset[int]) -> int:
    """How many of an object's k data shards sat on the `lost` drives:
    the indices 1..k that no kept drive's xl.meta names (0 where no kept
    drive knows the key)."""
    if not lost:
        return 0
    kept = {placed[0] for i, d in enumerate(drives) if i not in lost
            and (placed := _placed(d, bucket, key)) is not None}
    return sum(1 for idx in range(1, k + 1) if idx not in kept) \
        if kept else 0


def copies_missing(drive: str, bucket: str,
                   objects: list[tuple[str, int, int]], k: int,
                   block: int) -> int:
    """How many of the (single-part) objects this ONE drive does not
    hold as the reference would have left them there: no xl.meta, no
    part file, or a part file of another length than the reference's."""
    missing = 0
    for key, size, _ in objects:
        placed = _placed(drive, bucket, key)
        try:
            have = -1 if placed is None else os.path.getsize(os.path.join(
                drive, bucket, key, placed[1], "part.1"))
        except OSError:
            have = -1
        missing += have != reference.shard_file_bytes(size, k, block)
    return missing


def check(drives: list[str], bucket: str,
          objects: list[tuple[str, int, int]], body_of, k: int, m: int,
          block: int, part_size: int = 0, sets: int = 1,
          lost: frozenset[int] = frozenset()) -> dict:
    """Counts of what differs from the reference over `objects`.
    `body_of(size, off)` gives an object's bytes; `part_size` > 0 for
    objects written as multipart uploads of that part size; `lost` the
    positions in `drives` the mix's fault took out."""
    out = {"objects_checked": 0, "shard_files_checked": 0,
           "frames_checked": 0, "shard_files_missing": 0,
           "shard_frames_differ": 0, "digest_frames_differ": 0,
           "lost_copies_present": 0}
    per_set = len(drives) // sets
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
        stored = [_stored(d, bucket, key, pn) for d in drives]
        held = [sum(1 for i in range(s * per_set, (s + 1) * per_set)
                    if i not in lost and stored[i] is not None)
                for s in range(sets)]
        home = held.index(max(held))
        seen: set[int] = set()
        for i, got in enumerate(stored):
            if i in lost:
                out["lost_copies_present"] += got is not None
                continue
            if i // per_set != home:
                out["shard_files_missing"] += got is not None
                continue
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
