"""The one general traffic generator: a traffic mix is a JSON file of
parameters under benchmark/traffic/, this module turns it and --seed
into per-client operation streams. A later cell adds a file, not code.

File (all sizes in bytes):

    {"loop": "closed" | "open",
     "timeout_s": 120,
     "groups": [{
        "name": "main", "clients": 8,
        "sequence": ["PUT", "GET"]  |  "weights": {"GET": 45, "PUT": 15},
        "sizes": {"cycle": [n, ...]} | {"weighted": [[n, w], ...]},
        "keys": {"ring": 8},             # per-client ring PUTs walk round
        "read": "last_put" | "ring" | {"zipf": 0.99},
        "rate_per_s": 0, "arrivals": "poisson" | "uniform",
                  # > 0: this group offers that many operations a second
                  # whatever the loop (due times from the window's opening)
        "range_bytes": 65536, "part_size": 5242880}],
     "preload": {"per_client": 0,        # operations of its stream a client
                                         # runs before the warm-up; a group
                                         # that never writes fills that many
                                         # ring slots with them
                 "fill": 0},             # every client of every group PUTs
                                         # ring slots 0..fill-1 first, sizes
                                         # from its stream; its window PUTs
                                         # then go on from slot `fill`
     "verify": {"at_rest_sample": 6},    # window PUTs compared at rest
     "trace_slice_s": 0.5,               # the traced slice, the window's
                                         # last (harness/trace_reduce.py
                                         # says what a second of it costs)
     "faults": {"offline_drives": [2, 5]},   # FAULTS below; run.py's
                                         # apply_faults says what each does
     "rehearse": {...}}                  # overrides for --rehearse

A timed heal (harness/heal.py) is the fault pair

     "faults": {"wipe_drive": 7,
                "heal": {"poll_s": 0.1,          # heal-status every ...
                         "trace_start_s": 5.0}}  # --trace 1: the slice of
                                         # `trace_slice_s` seconds starts
                                         # this long after the window opens

(both numbers required). Its window is not `--seconds` of the groups'
operations but ONE admin heal of the bucket, sent at once after the wipe
and polled to `done` within `timeout_s`; the groups only say what the
preload writes and the warm-up reads.

Operations: PUT GET HEAD DELETE RANGE MULTIPART. Every seed gives each
client the same multiset of sizes per cycle, in another order.
"""

from __future__ import annotations

import bisect
import hashlib
import json
import random
from dataclasses import dataclass, field

OPS = ("PUT", "GET", "HEAD", "DELETE", "RANGE", "MULTIPART")
WRITES = ("PUT", "MULTIPART")
FAULTS = ("offline_drives", "remove_object_copies", "remove_drive_copies",
          "wipe_drive", "heal")   # drives are numbered from 1, as d<i>
LOST_DRIVE_FAULTS = FAULTS[:2]    # leave a drive without copies for good
OFFSET_SPAN = 1 << 20      # bodies are base[off:off+size], off < this


class TrafficError(ValueError):
    pass


@dataclass
class Group:
    name: str
    clients: int
    sequence: list[str]
    weights: dict[str, float]
    sizes: list[int]                 # one cycle, unshuffled
    ring: int
    read: str | dict
    rate_per_s: float = 0.0
    arrivals: str = "poisson"
    range_bytes: int = 65536
    part_size: int = 5 << 20

    @property
    def kinds(self) -> list[str]:
        return list(dict.fromkeys(self.sequence or self.weights))

    @property
    def writes(self) -> bool:
        return any(k in WRITES for k in self.kinds)


@dataclass
class Traffic:
    name: str
    loop: str
    timeout_s: float
    groups: list[Group]
    preload_per_client: int = 0
    at_rest_sample: int = 6
    trace_slice_s: float = 0.5
    faults: dict = field(default_factory=dict)
    # Out of the repr, which the accepted mixes' parse is pinned by
    # (tests/test_traffic.py): a mix that leaves it out is unchanged.
    preload_fill: int = field(default=0, repr=False)

    @property
    def max_size(self) -> int:
        return max(max(g.sizes) for g in self.groups)

    @property
    def writes(self) -> bool:
        """Whether the window itself can write (a read with nothing to
        read turns into a PUT, but a preload leaves none such)."""
        return any(g.writes for g in self.groups)


def _need(cond: bool, what: str) -> None:
    if not cond:
        raise TrafficError(what)


def _sizes(spec: dict) -> list[int]:
    if "cycle" in spec:
        out = [int(n) for n in spec["cycle"]]
    elif "weighted" in spec:
        out = [int(n) for n, w in spec["weighted"] for _ in range(int(w))]
    else:
        raise TrafficError(f"sizes needs 'cycle' or 'weighted': {spec}")
    _need(out and all(n > 0 for n in out), f"sizes must be positive: {spec}")
    return out


def parse(name: str, doc: dict, rehearse: bool = False) -> Traffic:
    doc = dict(doc)
    if rehearse:
        over = doc.get("rehearse", {})
        groups = over.get("groups", {})
        doc.update({k: v for k, v in over.items() if k != "groups"})
        doc["groups"] = [dict(g, **groups.get(g.get("name", ""), {}))
                         for g in doc["groups"]]
    loop = doc.get("loop", "closed")
    _need(loop in ("closed", "open"), f"loop is closed or open, not {loop}")
    groups = []
    for g in doc.get("groups", []):
        seq = [s.upper() for s in g.get("sequence", [])]
        weights = {k.upper(): float(v)
                   for k, v in g.get("weights", {}).items()}
        _need(bool(seq) != bool(weights),
              "a group gives 'sequence' or 'weights', one of them")
        for op in seq or weights:
            _need(op in OPS, f"unknown operation {op}")
        read = g.get("read", "last_put")
        _need(read in ("last_put", "ring")
              or (isinstance(read, dict) and "zipf" in read),
              f"read is last_put, ring or {{'zipf': s}}: {read}")
        grp = Group(
            name=g.get("name", f"g{len(groups)}"),
            clients=int(g["clients"]), sequence=seq, weights=weights,
            sizes=_sizes(g["sizes"]), ring=int(g["keys"]["ring"]),
            read=read, rate_per_s=float(g.get("rate_per_s", 0.0)),
            arrivals=g.get("arrivals", "poisson"),
            range_bytes=int(g.get("range_bytes", 65536)),
            part_size=int(g.get("part_size", 5 << 20)))
        _need(grp.clients > 0 and grp.ring > 0, "clients and ring > 0")
        _need(grp.arrivals in ("poisson", "uniform"),
              f"arrivals is poisson or uniform: {grp.arrivals}")
        _need(grp.rate_per_s >= 0, "rate_per_s >= 0")
        if loop == "open":
            _need(grp.rate_per_s > 0, "an open loop needs rate_per_s")
        groups.append(grp)
    _need(bool(groups), "a traffic mix needs at least one group")
    faults = dict(doc.get("faults", {}))
    for key in faults:
        _need(key in FAULTS, f"unknown fault {key}: one of {FAULTS}")
    if "heal" in faults:
        heal = faults["heal"]
        _need(isinstance(heal, dict)
              and set(heal) == {"poll_s", "trace_start_s"},
              "faults.heal is {'poll_s': s, 'trace_start_s': s}")
        _need(isinstance(faults.get("wipe_drive"), int),
              "faults.heal heals the ONE drive faults.wipe_drive names")
    preload, verify = doc.get("preload", {}), doc.get("verify", {})
    fill = int(preload.get("fill", 0))
    _need(all(0 <= fill <= g.ring for g in groups),
          f"preload.fill {fill} is 0 ... every group's ring")
    return Traffic(
        name=name, loop=loop, timeout_s=float(doc.get("timeout_s", 120)),
        groups=groups,
        preload_per_client=int(preload.get("per_client", 0)),
        at_rest_sample=int(verify.get("at_rest_sample", 6)),
        trace_slice_s=float(doc.get("trace_slice_s", 0.5)),
        faults=faults, preload_fill=fill)


def load(path: str, name: str, rehearse: bool = False) -> Traffic:
    with open(path) as f:
        return parse(name, json.load(f), rehearse)


# --- per-client streams -------------------------------------------------------


def sub_seed(seed: int, *parts) -> int:
    h = hashlib.sha256("/".join(str(p) for p in (seed,) + parts).encode())
    return int.from_bytes(h.digest()[:8], "little")


@dataclass
class Op:
    kind: str
    key: str
    size: int          # object size (PUT/MULTIPART), else of the target
    off: int           # body = base[off:off+size] for writes
    due_s: float = 0.0  # open loop: seconds after window open


class _Zipf:
    """P(rank r) ~ 1/r^s, inverse-CDF bisect (after tools/loadgen.py)."""

    def __init__(self, s: float, n: int):
        weights = [1.0 / ((r + 1) ** s) for r in range(n)]
        total = sum(weights)
        acc, self._cdf = 0.0, []
        for w in weights:
            acc += w / total
            self._cdf.append(acc)

    def sample(self, rng: random.Random) -> int:
        return min(bisect.bisect_left(self._cdf, rng.random()),
                   len(self._cdf) - 1)


class ClientStream:
    """The operations of one client, in order, from (seed, group,
    client). `written` is the driver's record of which ring slots hold
    an object (preload and acknowledged PUTs); reads pick from it.
    `fill`: in a group that never writes, a read turns into the PUT of
    the next ring slot until that many slots hold an object (the
    preload of a read-only window)."""

    def __init__(self, seed: int, group: Group, client: int, fill: int = 0):
        self.group = group
        self.client = client
        self._fill = 0 if group.writes else min(fill, group.ring)
        self.rng = random.Random(sub_seed(seed, group.name, client))
        self._sizes: list[int] = []
        self._slot = 0
        self._step = 0
        self._due = 0.0
        self.last_put: str | None = None
        self.written: dict[str, int] = {}     # key -> size
        self._zipf = (_Zipf(float(group.read["zipf"]), group.ring)
                      if isinstance(group.read, dict) else None)
        kinds, w = zip(*group.weights.items()) if group.weights else ((), ())
        self._kinds, self._w = list(kinds), list(w)

    def open_window(self) -> None:
        """Due times count from the window's opening, whatever set-up
        (preload, warm-up) drew from the stream before."""
        self._due = 0.0

    def key(self, slot: int) -> str:
        return f"{self.group.name}/c{self.client:03d}/k{slot:04d}"

    def next_size(self) -> int:
        if not self._sizes:
            self._sizes = list(self.group.sizes)
            self.rng.shuffle(self._sizes)
        return self._sizes.pop()

    def fill_ops(self, n: int) -> list[Op]:
        """The PUTs of ring slots 0..n-1, in order, sizes and bodies
        drawn as a window PUT draws them; the slot counter is left at n,
        so that the window's PUTs write new names."""
        ops = [Op("PUT", self.key(slot), self.next_size(),
                  self.rng.randrange(OFFSET_SPAN)) for slot in range(n)]
        self._slot = n
        return ops

    def warm_ops(self, size: int) -> list[Op]:
        """A weighted group's warm-up: one operation of each kind it
        sends, all on the next ring slot, written first at `size` bytes,
        a DELETE last; so every size is written and read once whatever
        the weights would have drawn."""
        key = self.key(self._slot % self.group.ring)
        self._slot += 1
        kinds = sorted(self.group.kinds,
                       key=lambda k: (k not in WRITES, k == "DELETE"))
        return [Op(k, key, size, self.rng.randrange(OFFSET_SPAN)
                   if k in WRITES else 0) for k in kinds]

    def _read_key(self) -> str | None:
        g = self.group
        if g.read == "last_put":
            return self.last_put
        if not self.written:
            return None
        if self._zipf is not None:
            k = self.key(self._zipf.sample(self.rng))
            return k if k in self.written else None
        keys = sorted(self.written)
        return keys[self.rng.randrange(len(keys))]

    def next(self) -> Op:
        g = self.group
        if g.sequence:
            kind = g.sequence[self._step % len(g.sequence)]
        else:
            kind = self.rng.choices(self._kinds, self._w)[0]
        self._step += 1
        if g.rate_per_s:
            gap = g.clients / g.rate_per_s
            self._due += (self.rng.expovariate(1.0 / gap)
                          if g.arrivals == "poisson" else gap)
        if kind in WRITES:
            key = self.key(self._slot % g.ring)
            self._slot += 1
            return Op(kind, key, self.next_size(),
                      self.rng.randrange(OFFSET_SPAN), self._due)
        key = self._read_key()
        if key is None or len(self.written) < self._fill:
            # Nothing to read yet: write first, as a client would.
            key = self.key(self._slot % g.ring)
            self._slot += 1
            return Op("PUT", key, self.next_size(),
                      self.rng.randrange(OFFSET_SPAN), self._due)
        return Op(kind, key, self.written.get(key, 0), 0, self._due)


def streams(seed: int, traffic: Traffic) -> list[ClientStream]:
    return [ClientStream(seed, g, c, traffic.preload_per_client)
            for g in traffic.groups for c in range(g.clients)]


def base_buffer(seed: int, max_size: int) -> bytes:
    """The bytes every body is a slice of, from the seed."""
    import numpy as np
    return np.random.default_rng(sub_seed(seed, "base")).bytes(
        max_size + OFFSET_SPAN)
