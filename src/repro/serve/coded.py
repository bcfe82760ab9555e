"""Coded straggler-tolerant serving: LCC-protected decode state.

The paper's all-to-all encode exists so decentralized computation survives
failures; this module wires it into the continuous-batching engine. The
decode-path state (every layer's KV-cache slab + the per-slot decode state
holding the logits-contribution counters, token buffers and PRNG streams)
is flattened to field limbs, sharded K ways, and encoded into **N = K + R
coded replicas** with the padded Lagrange/Vandermonde generator
(``repro.coded.lcc_encode`` — one universal prepare-and-shoot all-to-all
encode; with ``mesh=`` the same generator executes through
``dist.collectives.ir_encode_jit`` as ppermute rounds on an N-wide host
axis). Each coded shard is owned by one simulated "host".

A :class:`FaultInjector` kills hosts at scheduled decode ticks (or a
:class:`ProcessHostPool` host — a real OS process holding its shard —
is SIGKILLed). The engine detects the fault at the next chunk sync,
:class:`CodedServeGuard` reconstructs the exact chunk-start state from any
K of the surviving shards via Lagrange interpolation on the device
(``repro.coded.lcc_decode_device``: the K surviving uint32 shards are
uploaded and interpolated by one compiled program, compiled at the first
snapshot of a shape so that no recovery compiles), and the chunk replays
deterministically —
requests in flight on the dead host are **recovered, not dropped**, and
the emitted token stream is bit-identical to an unfailed run.

A snapshot copies to the host only what changed. The K data shards (the
limbs) of the previous snapshot stay on the device; one compiled program
compares the new ones with them block by block of :data:`SNAPSHOT_BLOCK`
columns (coded column s is G · x[:, s], so a block whose limbs did not
change has coded words that did not either), a compiled gather copies the
changed blocks of the new coded array, and the hosts patch them into the
shards they hold. Whole shards go, into fresh arrays, at the first snapshot
of a shape and after every recovery.

Observability: ``serve.recoveries`` (hosts recovered from), ``serve.
recovery_us`` (reconstruction latency histogram), ``serve.snapshots``.
``serve.snapshot_bytes_copied`` (bytes a snapshot copied to the host) and
``serve.snapshots_full`` (snapshots that stored whole shards). With a tracer
attached, each snapshot is a ``serve.snapshot`` span (``tick``) with
children ``serve.snapshot.device`` (limbs, their comparison with the
previous snapshot's, and the encode, up to the coded array being ready;
``words``), ``serve.snapshot.to_host`` (``bytes`` copied, ``blocks``
copied, ``full`` 1 when whole shards went) and ``serve.snapshot.store``
(``shards``: hosts stored or patched); each recovery is a
``serve.recovery`` span (``hosts``, ``tick``) with children
``serve.recovery.fetch`` (``bytes``, ``responders``; the host shards),
``serve.recovery.decode`` (``words``; upload of the K survivors and the
device interpolation, up to ready) and ``serve.recovery.to_device``
(``bytes``; the unshard into the engine's state, up to ready).
"""

from __future__ import annotations

import base64
import collections
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from repro.coded.lagrange_compute import (
    build_lcc,
    compile_lcc_decode_device,
    lcc_decode_device,
    lcc_encode,
    lcc_encode_collective,
    lcc_pad,
)
from repro.coded.rs_checkpoint import shard_state_limbs, unshard_state_limbs
from repro.core.field import NTT
from repro.obs.trace import optional_span


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------


@dataclass
class FaultInjector:
    """Deterministic fault schedule: kill host ``h`` once decode tick ``t``
    has completed. ``due(now)`` returns the not-yet-fired kills with
    ``t < now`` (the chunk that crossed tick t detects them at its sync)."""

    kills: tuple[tuple[int, int], ...]  # (tick, host) pairs
    _fired: set = field(default_factory=set)

    def due(self, now_tick: int) -> list[tuple[int, int]]:
        out = []
        for i, (t, h) in enumerate(self.kills):
            if i not in self._fired and t < now_tick:
                self._fired.add(i)
                out.append((t, h))
        return out

    @property
    def injected(self) -> int:
        """Faults fired so far."""
        return len(self._fired)


# ---------------------------------------------------------------------------
# host processes (the SIGKILL-able variant)
# ---------------------------------------------------------------------------

#: the whole host program: store one shard, serve it back on request. No
#: repro imports — a host is just memory that can die.
_HOST_LOOP = r"""
import base64, sys
store = None
for line in sys.stdin:
    line = line.strip()
    if not line:
        continue
    cmd, _, arg = line.partition(" ")
    if cmd == "put":
        store = bytearray(base64.b64decode(arg))
        sys.stdout.write("ok\n")
    elif cmd == "get":
        sys.stdout.write(("none" if store is None else base64.b64encode(store).decode()) + "\n")
    elif cmd == "patch" and store is not None:
        # block k's words start at min(k * block, S - block)
        block, ids, words = arg.split(" ")
        size, ids, words = 4 * int(block), memoryview(base64.b64decode(ids)).cast("I"), base64.b64decode(words)
        for i, k in enumerate(ids):
            at = min(k * size, len(store) - size)
            store[at:at + size] = words[i * size:(i + 1) * size]
        sys.stdout.write("ok\n")
    elif cmd == "quit":
        break
    else:
        sys.stdout.write("err\n")
    sys.stdout.flush()
"""


class ProcessHostPool:
    """N coded-shard hosts, each a separate OS process holding its shard in
    its own memory over a line pipe — so a ``SIGKILL`` is a *real* host
    loss, not a simulation flag. Store/fetch failures (dead pipe, EOF)
    report the host dead rather than raising."""

    def __init__(self, n_hosts: int):
        self.procs = [
            subprocess.Popen(
                [sys.executable, "-c", _HOST_LOOP],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                text=True,
                bufsize=1,
            )
            for _ in range(n_hosts)
        ]

    def __len__(self) -> int:
        return len(self.procs)

    def alive(self, host: int) -> bool:
        return self.procs[host].poll() is None

    def store(self, host: int, shard: np.ndarray) -> bool:
        p = self.procs[host]
        if p.poll() is not None:
            return False
        payload = base64.b64encode(
            np.ascontiguousarray(shard, dtype=np.uint32).tobytes()
        ).decode()
        try:
            p.stdin.write(f"put {payload}\n")
            p.stdin.flush()
            return p.stdout.readline().strip() == "ok"
        except (BrokenPipeError, OSError, ValueError):
            return False

    def patch(self, host: int, block_ids: np.ndarray, values: np.ndarray) -> bool:
        """Overwrite blocks ``block_ids`` of the host's shard with ``values``
        ((n, block) uint32), as :func:`write_blocks` does."""
        p = self.procs[host]
        if p.poll() is not None:
            return False
        ids = base64.b64encode(np.asarray(block_ids, "<u4").tobytes()).decode()
        words = base64.b64encode(np.ascontiguousarray(values, "<u4").tobytes()).decode()
        try:
            p.stdin.write(f"patch {values.shape[-1]} {ids} {words}\n")
            p.stdin.flush()
            return p.stdout.readline().strip() == "ok"
        except (BrokenPipeError, OSError, ValueError):
            return False

    def fetch(self, host: int) -> np.ndarray | None:
        p = self.procs[host]
        if p.poll() is not None:
            return None
        try:
            p.stdin.write("get\n")
            p.stdin.flush()
            line = p.stdout.readline().strip()
        except (BrokenPipeError, OSError, ValueError):
            return None
        if not line or line in ("none", "err"):
            return None
        return np.frombuffer(base64.b64decode(line), dtype=np.uint32).copy()

    def kill(self, host: int, sig: int = signal.SIGKILL) -> None:
        p = self.procs[host]
        if p.poll() is None:
            p.send_signal(sig)
            p.wait()  # the host is DEAD before the engine carries on

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.stdin.write("quit\n")
                    p.stdin.flush()
                except (BrokenPipeError, OSError, ValueError):
                    pass
                p.terminate()
            p.wait()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# the delta copy: which blocks changed, and their coded words to the host
# ---------------------------------------------------------------------------

#: words in a column block, the unit in which a snapshot finds what changed
#: and copies it. A decode chunk writes runs of a few thousand contiguous
#: limbs (a few positions of one slot's K or V rows in one layer), so much
#: larger blocks copy mostly unchanged words and much smaller ones multiply
#: the flags and the gather's slices; a multiple of the TPU's 128 lanes.
SNAPSHOT_BLOCK = 1024

#: blocks one call of the compiled gather copies (4 MiB at N = 4): a decode
#: chunk's changes take a few calls, the last padded by at most this many
#: blocks, and a whole state a few hundred
GATHER_BLOCKS = 256

#: gather calls whose copies to the host run ahead of the host's reads
_IN_FLIGHT = 8


def _changed_blocks(x, base, *, block):
    """(n_blocks,) bool: whether any row of the (rows, S) array ``x``
    differs from ``base`` in each column block. The last block ends at S,
    so it overlaps the one before when S is no multiple of ``block``."""
    rows, S = x.shape
    differs = x != base
    whole = S // block
    flags = differs[:, : whole * block].reshape(rows, whole, block).any(axis=(0, 2))
    if S % block:
        flags = jnp.append(flags, differs[:, S - block:].any())
    return flags


def _gather_blocks(coded, block_ids, *, block):
    """(N, len(block_ids), block): the words of each listed column block."""
    starts = jnp.minimum(block_ids * block, coded.shape[1] - block)
    return jax.vmap(
        lambda at: jax.lax.dynamic_slice_in_dim(coded, at, block, axis=1),
        out_axes=1,
    )(starts)


_changed_blocks_jit = jax.jit(_changed_blocks, static_argnames="block")
_gather_blocks_jit = jax.jit(_gather_blocks, static_argnames="block")


def write_blocks(row: np.ndarray, block_ids: np.ndarray, values: np.ndarray) -> None:
    """Write column block ``block_ids[i]`` of the (S,) array ``row`` from
    ``values[i]`` ((n, block)), in place. ``block_ids`` ascend, so only the
    last can be the block that ends at S."""
    block = values.shape[-1]
    whole = row.size // block
    head = row[: whole * block].reshape(whole, block)
    if block_ids[-1] < whole:
        head[block_ids] = values
    else:
        head[block_ids[:-1]] = values[:-1]
        row[-block:] = values[-1]


class BlockCopy:
    """The delta copy's programs for one state shape, compiled when made:
    which column blocks of the (K, S) data shards changed against the
    previous snapshot's, and those blocks of the (N, S) coded array
    gathered on the device and copied to the host :attr:`per_call` blocks a
    call."""

    def __init__(self, shards: jax.Array, coded: jax.Array):
        self.N, S = coded.shape
        self.block = min(SNAPSHOT_BLOCK, S)
        self.n_blocks = -(-S // self.block)
        self.per_call = min(GATHER_BLOCKS, self.n_blocks)
        self.all_ids = np.arange(self.n_blocks, dtype=np.int32)
        _changed_blocks_jit.lower(shards, shards, block=self.block).compile()
        _gather_blocks_jit.lower(
            coded, np.zeros(self.per_call, np.int32), block=self.block
        ).compile()

    def changed(self, shards: jax.Array, base: jax.Array) -> np.ndarray:
        """Ascending ids of the blocks where ``shards`` differ from ``base``."""
        flags = _changed_blocks_jit(shards, base, block=self.block)
        return np.flatnonzero(np.asarray(flags)).astype(np.int32)

    def bytes_copied(self, n: int) -> int:
        """Bytes the gather calls for ``n`` blocks copy, padding included."""
        return -(-n // self.per_call) * self.per_call * self.N * self.block * 4

    def fetch(self, coded: jax.Array, block_ids: np.ndarray):
        """Yield (ids, (N, len(ids), block) host uint32 words) a gather call
        at a time; the last call is padded with its last id."""
        pending = collections.deque()
        for at in range(0, len(block_ids), self.per_call):
            ids = block_ids[at : at + self.per_call]
            words = _gather_blocks_jit(
                coded, np.pad(ids, (0, self.per_call - ids.size), mode="edge"),
                block=self.block,
            )
            words.copy_to_host_async()
            pending.append((ids, words))
            if len(pending) == _IN_FLIGHT:
                ids, words = pending.popleft()
                yield ids, np.asarray(words)[:, : ids.size]
        while pending:
            ids, words = pending.popleft()
            yield ids, np.asarray(words)[:, : ids.size]


# ---------------------------------------------------------------------------
# the K-of-N decode group
# ---------------------------------------------------------------------------


class CodedDecodeGroup:
    """The N = K + R coded shard holders and the any-K-of-N reconstruction.

    A "host" is either an in-memory slot (default) or one
    :class:`ProcessHostPool` child process. The group hands coded shard j
    to host j (host uint32 arrays; :meth:`store` whole, :meth:`patch` the
    blocks that changed), tracks which hosts
    are alive, and rebuilds all K data shards from the first K survivors
    via Lagrange interpolation on the device
    (``repro.coded.lcc_decode_device``).

    ``tracer`` (set by :meth:`CodedServeGuard.attach`) puts the fetch and
    the decode of :meth:`reconstruct` under spans of their own."""

    def __init__(self, plan, hosts: ProcessHostPool | None = None):
        if hosts is not None and len(hosts) != plan.N:
            raise ValueError(
                f"host pool has {len(hosts)} hosts, need N={plan.N}"
            )
        self.plan = plan
        self.hosts = hosts
        self.alive: set[int] = set(range(plan.N))
        self._mem: dict[int, np.ndarray] = {}
        self.tracer = None

    def store(self, coded: np.ndarray) -> None:
        """Hand coded row j to host j, in place of what it held (in-memory
        hosts keep row j itself, not a copy); a host found dead mid-store is
        dropped from the alive set, not raised on."""
        self._mem = {}
        for j in sorted(self.alive):
            if self.hosts is not None:
                if not self.hosts.store(j, coded[j]):
                    self.alive.discard(j)
            else:
                self._mem[j] = np.asarray(coded[j], dtype=np.uint32)

    def patch(self, block_ids: np.ndarray, values: np.ndarray) -> None:
        """Overwrite column blocks ``block_ids`` of the shard host j holds
        with ``values[j]`` ((N, n, block) uint32; :func:`write_blocks`), in
        place; a host found dead mid-patch is dropped, not raised on."""
        for j in sorted(self.alive):
            if self.hosts is not None:
                if not self.hosts.patch(j, block_ids, values[j]):
                    self.alive.discard(j)
            else:
                write_blocks(self._mem[j], block_ids, values[j])

    def kill(self, host: int) -> bool:
        """Take host down (SIGKILL when it is a process). Returns whether
        it was alive — dead hosts can't die twice."""
        if host not in self.alive:
            return False
        if self.hosts is not None:
            self.hosts.kill(host)
        self.alive.discard(host)
        return True

    def scan(self) -> list[int]:
        """Detect hosts that died without the injector's help (process
        pools only — an in-memory slot can't die by itself)."""
        if self.hosts is None:
            return []
        dead = [h for h in sorted(self.alive) if not self.hosts.alive(h)]
        self.alive.difference_update(dead)
        return dead

    def reconstruct(self) -> jax.Array:
        """All K data shards, bit-exact, from the first K surviving coded
        shards, as a (K, S) uint32 device array, ready. Raises RuntimeError
        when fewer than K survive — past the code's R-failure tolerance
        there is nothing to interpolate."""
        values, responders = [], []
        with optional_span(self.tracer, "serve.recovery.fetch") as sp:
            for j in sorted(self.alive):
                if self.hosts is not None:
                    v = self.hosts.fetch(j)
                    if v is None:  # died between scan and fetch
                        self.alive.discard(j)
                        continue
                else:
                    v = self._mem.get(j)
                    if v is None:
                        continue
                values.append(v)
                responders.append(j)
                if len(responders) == self.plan.K:
                    break
            if sp is not None:
                sp.attrs.update(bytes=sum(v.nbytes for v in values),
                                responders=len(responders))
        if len(responders) < self.plan.K:
            raise RuntimeError(
                f"{len(responders)} coded shards survive, need "
                f"K={self.plan.K} (R={self.plan.R} tolerates at most "
                f"{self.plan.R} lost hosts)"
            )
        with optional_span(self.tracer, "serve.recovery.decode",
                           words=sum(v.size for v in values)):
            return jax.block_until_ready(
                lcc_decode_device(self.plan, values, responders)
            )


# ---------------------------------------------------------------------------
# the guard
# ---------------------------------------------------------------------------


class CodedServeGuard:
    """``train.elastic.CodedStateGuard``'s pattern extended to the serving
    engine: snapshot the decode-path state as N = K + R LCC shards every
    decode chunk, and rebuild the exact chunk-start state from any K
    survivors after a host loss.

    Wire it in with ``ContinuousEngine.serve(..., guard=guard)``; the
    engine calls :meth:`snapshot` before each decode chunk, :meth:`poll`
    at the chunk sync, and :meth:`recover` + chunk replay when a host died.

    ``hosts=`` (a :class:`ProcessHostPool`) stores each shard in its own
    OS process — the injector then delivers real SIGKILLs, and externally
    killed hosts are detected at :meth:`poll` too. ``mesh=``/``axis=``
    (an N-wide mesh axis) routes the encode through the ScheduleIR mesh
    executor ``dist.collectives.ir_encode_jit`` instead of the
    single-program jit."""

    def __init__(
        self,
        K: int,
        R: int = 1,
        p: int = 1,
        q: int = NTT,
        injector: FaultInjector | None = None,
        hosts: ProcessHostPool | None = None,
        mesh=None,
        axis: str | None = None,
    ):
        if R < 1:
            raise ValueError("coded serving needs R ≥ 1 parity shards")
        self.plan = build_lcc(K, p=p, q=q, R=R)
        self.K, self.R, self.N = K, R, K + R
        self.injector = injector
        self.group = CodedDecodeGroup(self.plan, hosts=hosts)
        if mesh is not None:
            if axis is None:
                raise ValueError("mesh= requires axis=")
            self._encode = lcc_encode_collective(mesh, axis, self.plan)
        else:
            plan = self.plan

            def coded_snapshot_encode(xp):
                return lcc_encode(plan, xp[: plan.K])

            self._encode = jax.jit(coded_snapshot_encode)
        self._meta = None
        #: the (K, S) data shards whose encode the live hosts hold
        self._base: jax.Array | None = None
        #: by (K, S): the programs of the first snapshot of each shape
        self._copies: dict[tuple[int, ...], BlockCopy] = {}
        self._tick = -1
        self._metrics = None
        self._tracer = None
        #: every fault seen: (host, decode tick at detection)
        self.faults: list[tuple[int, int]] = []
        self.recoveries = 0
        self.requests_recovered = 0
        self.recovery_us: list[float] = []
        self.snapshots = 0

    # -- engine plumbing ----------------------------------------------------
    def attach(self, metrics, tracer) -> None:
        self._metrics, self._tracer = metrics, tracer
        self.group.tracer = tracer

    @property
    def alive(self) -> set[int]:
        return self.group.alive

    @property
    def injected_faults(self) -> int:
        """Scheduled kills fired (injector) or external deaths detected."""
        return self.injector.injected if self.injector is not None else len(self.faults)

    def snapshot(self, cache, state, tick: int) -> None:
        """Encode the decode-path state ((cache, state) pytree → limbs →
        K shards → N coded shards) and bring host j's shard to coded row j:
        the column blocks whose limbs differ from the previous snapshot's
        are copied and patched in, or whole shards are stored, into
        fresh arrays, at the first snapshot of a shape and after a
        recovery. The first snapshot of a shape also compiles the
        comparison, the gather and the recovery's decode for it, so that
        later snapshots and a recovery compile nothing."""
        tracer = self._tracer
        # dropped until the hosts hold the new coded array (a snapshot cut
        # short leaves the next one to store whole shards), and freed before
        # the encode, whose program needs the room
        base, self._base = self._base, None
        with optional_span(tracer, "serve.snapshot", tick=tick):
            with optional_span(tracer, "serve.snapshot.device") as sp:
                shards, meta = shard_state_limbs((cache, state), self.K)
                copy = self._copies.get(shards.shape)
                full = base is None or base.shape != shards.shape
                ids = None if full else copy.changed(shards, base)
                del base
                coded = jax.block_until_ready(
                    self._encode(lcc_pad(self.plan, shards))
                )
                if copy is None:
                    copy = self._copies[shards.shape] = BlockCopy(shards, coded)
                    compile_lcc_decode_device(self.plan, shards.shape[1])
                if full:
                    ids = copy.all_ids
                if sp is not None:
                    sp.attrs["words"] = int(shards.size)
            copied = copy.bytes_copied(ids.size)
            with optional_span(tracer, "serve.snapshot.to_host", bytes=copied,
                               blocks=int(ids.size), full=int(full)):
                if full:
                    rows = np.empty(coded.shape, np.uint32)
                    for part, words in copy.fetch(coded, ids):
                        for j in range(self.N):
                            write_blocks(rows[j], part, words[j])
                else:
                    parts = list(copy.fetch(coded, ids))
            self._meta, self._tick = meta, tick
            with optional_span(tracer, "serve.snapshot.store") as sp:
                if full:
                    self.group.store(rows)
                else:
                    for part, words in parts:
                        self.group.patch(part, words)
                if sp is not None:
                    sp.attrs["shards"] = len(self.group.alive)
        self._base = shards
        self.snapshots += 1
        if self._metrics is not None:
            self._metrics.counter("serve.snapshots").inc()
            self._metrics.counter("serve.snapshot_bytes_copied").inc(copied)
            self._metrics.counter("serve.snapshots_full").inc(int(full))

    def poll(self, now_tick: int) -> list[int]:
        """Fire due injector kills (SIGKILL when hosts are processes) and
        detect externally dead hosts; returns hosts lost this chunk."""
        dead = []
        if self.injector is not None:
            for _t, h in self.injector.due(now_tick):
                if self.group.kill(h):
                    dead.append(h)
        dead.extend(self.group.scan())
        for h in dead:
            self.faults.append((h, now_tick))
        return dead

    def recover(self, dead: list[int], requests_in_flight: int = 0):
        """Rebuild the chunk-start (cache, state) bit-exactly from any K
        surviving coded shards (Lagrange interpolation on the device, then
        the unshard into the engine's state). Raises RuntimeError
        once fewer than K shards survive — beyond the code's tolerance.
        The next snapshot stores whole shards into fresh arrays: an array a
        host held at a recovery is never written again."""
        if self._meta is None:
            raise RuntimeError("no snapshot taken before recovery")
        self._base = None
        tracer = self._tracer
        with optional_span(
            tracer, "serve.recovery", hosts=str(sorted(dead)), tick=self._tick
        ):
            t0 = time.perf_counter()
            X = self.group.reconstruct()
            with optional_span(
                tracer, "serve.recovery.to_device", bytes=X.size * 4
            ):
                cache, state = unshard_state_limbs(X, self._meta)
                jax.block_until_ready((cache, state))
            dur_us = (time.perf_counter() - t0) * 1e6
        self.recoveries += len(dead)
        self.requests_recovered += requests_in_flight
        self.recovery_us.append(dur_us)
        if self._metrics is not None:
            self._metrics.counter("serve.recoveries").inc(len(dead))
            self._metrics.histogram("serve.recovery_us").observe(dur_us)
        return cache, state

    def stats(self) -> dict:
        """JSON-ready recovery block for the benchmark record."""
        us = sorted(self.recovery_us)
        return {
            "K": self.K,
            "R": self.R,
            "n_hosts": self.N,
            "injected_faults": self.injected_faults,
            "recoveries": self.recoveries,
            "requests_recovered": self.requests_recovered,
            "snapshots": self.snapshots,
            "recovery_us": {
                "p50": float(np.percentile(us, 50)) if us else 0.0,
                "p99": float(np.percentile(us, 99)) if us else 0.0,
            },
        }
