"""Fault tolerance: checkpoint/restore, leader election, replica failover.

Mirrors the paper's §4.2 persistence design: "the [replicated] instances
perform leader election using ZooKeeper, and the winner proceeds to write
its results" every five minutes; frontends poll for updated results; on a
cold restart they serve the most-recently persisted state immediately.

Implementation: atomic-rename checkpoints (npz payload + json manifest),
keep-N retention, deterministic leader election over live replica ids (the
ZooKeeper-less equivalent: lowest live id wins — same liveness semantics,
suitable for the single-writer persistence pattern), and crash-recovery
restore that accepts any pytree template (elastic resharding lives in
``elastic.py``).

**Incremental (delta) snapshots** — the snapshot-chain format. A snapshot
step is either a *full* checkpoint (every leaf written whole) or a *delta*
against the immediately preceding snapshot (changed leading slots only —
MillWheel-style low-watermark checkpointing over the stores' known-dirty
slots; see ``core.stores.diff_leading_rows``). One manifest per step dir:

    MANIFEST.json = {
      "step":      int,
      "kind":      "full" | "delta",
      "base_step": int | null,   # delta only: the previous snapshot in the
                                 # chain (full or delta) it was diffed against
      "n_leaves":  int,          # pytree width (layout-mismatch guard)
      "raw_dtypes": {...},       # npz-unstorable dtypes, raw-viewed
      "sha256":    hex,          # over the arrays.npz bytes (torn/corrupt
                                 # detection during the chain walk)
      "nbytes":    int,          # arrays.npz size (delta-vs-full accounting)
      "time":      float, "meta": {...},
    }

arrays.npz holds ``leaf_{i}`` whole for a full (and for 0-d leaves always);
a delta stores ``leaf_{i}_idx`` (changed leading indices, i64) +
``leaf_{i}_val`` (the rows at those indices) per array leaf. Both full and
delta payloads are wrapped in a ``streaming.codec`` compressed container
(manifest ``codec``/``raw_sha256``/``raw_nbytes``; ``sha256``/``nbytes``
stay over the on-disk bytes so torn-write detection and the
``corrupt_snapshot`` injector are codec-oblivious); pre-codec raw-npz
checkpoints restore transparently.

Restore **chain-walk**: resolve the requested step back through
``base_step`` links to its base full (verifying each member's sha256), then
apply the deltas oldest-first onto the full's arrays. **Fallback rule**: a
torn/corrupt/missing chain member falls back to the newest *intact full*
snapshot at ``step <= requested`` — the caller observes an older restored
step and simply replays a longer firehose-log tail (``streaming.replay``
handles this transparently); only when no full verifies does restore raise.
**Retention rule**: the newest ``keep_n`` steps are kept, *expanded* by
every chain base a kept delta references — a full is never unlinked while a
retained delta still needs it, and a delta is never retained without its
base chain.

``full_interval=1`` (the default) disables deltas entirely — every save is
a full checkpoint, byte-identical behavior to the pre-delta manager. With
``full_interval=F``, each full is followed by up to ``F-1`` deltas. The
delta diff runs against an in-memory shadow of the last-saved leaves, so a
freshly constructed manager (e.g. after a process restart) always writes a
full first.

The manager is layout-agnostic: sharded engines route their shard-stacked
leaves (``core.sharded_engine.save_sharded_snapshot``) through the same
delta chains with no special casing, and live-serving snapshots taken
under overload control carry the controller's shed/latency counters in
``meta["overload"]`` so a restart resumes with its accounting intact.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from .. import obs
from ..core.stores import apply_row_delta, diff_leading_rows


def _codec():
    # Lazy: ``streaming.replay`` imports this module at its top level, so a
    # top-level import of ``streaming.codec`` here would make the package
    # import order circular. By first call, both packages are initialized.
    from ..streaming import codec as c
    return c


def _raw_view(a: np.ndarray) -> Tuple[np.ndarray, Optional[str]]:
    """npz cannot store ml_dtypes (bf16 etc): raw-view them, remember why."""
    if a.dtype.kind == "V" or a.dtype.name == "bfloat16":
        name = a.dtype.name
        return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint8), name
    return a, None


class CheckpointManager:
    def __init__(self, directory: str, keep_n: int = 3,
                 tmp_ttl_s: float = 3600.0, full_interval: int = 1,
                 codec: str = "zlib"):
        assert full_interval >= 1
        self.dir = directory
        self.keep_n = keep_n
        # payload codec (``streaming.codec``): full AND delta arrays.npz
        # blobs are wrapped in a compressed container; the manifest's
        # ``sha256``/``nbytes`` describe the on-disk (compressed) bytes —
        # ``corrupt_snapshot`` and the chain walk's integrity pass operate
        # on file bytes exactly as before — while ``raw_sha256``/
        # ``raw_nbytes`` describe the npz body inside. ``codec="raw"``
        # restores the pre-codec byte-identical format; either decodes.
        self.codec = codec
        # ``.tmp_*`` dirs older than this are debris from crashed writers
        # (a live writer holds its tmp dir only for the duration of one
        # save); retention removes them.
        self.tmp_ttl_s = tmp_ttl_s
        # delta-snapshot chain: every ``full_interval``-th save is a full,
        # the rest are deltas against the previous save (1 = fulls only).
        self.full_interval = full_interval
        self._shadow: Optional[List[np.ndarray]] = None  # last-saved leaves
        self._shadow_step: Optional[int] = None
        self._since_full = 0
        self.last_save_kind: Optional[str] = None
        self.last_save_bytes = 0
        # last restore's provenance: {requested, restored, chain_len,
        # fell_back} — ``fell_back`` means a torn/corrupt chain member was
        # skipped and an older intact full was used instead.
        self.last_restore: Dict[str, Any] = {}
        os.makedirs(directory, exist_ok=True)

    # -- paths --
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:012d}")

    def steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and os.path.exists(
                    os.path.join(self.dir, name, "MANIFEST.json")):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def manifest(self, step: Optional[int] = None) -> Dict:
        """The manifest of a checkpoint (its ``meta`` carries the log
        offset for §4.2-style catch-up recovery)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        with open(os.path.join(self._step_dir(step), "MANIFEST.json")) as f:
            return json.load(f)

    # -- save/restore --
    def save(self, step: int, tree: Any, meta: Optional[Dict] = None) -> str:
        """Atomic: write into a tmp dir, fsync, rename into place.

        With ``full_interval > 1`` the manager writes *delta* snapshots
        (changed leading slots only, diffed against the in-memory shadow of
        the previous save) between fulls — see the module docstring for the
        chain format. The decision is internal: callers keep calling
        ``save`` and the manifest records what was written.
        """
        with obs.span("persist.save"):
            leaves, treedef = jax.tree.flatten(tree)
            np_leaves = [np.asarray(x) for x in leaves]
            kind, base_step = "full", None
            if (self.full_interval > 1 and self._shadow is not None
                    and self._shadow_step is not None
                    and step > self._shadow_step
                    and self._since_full < self.full_interval - 1
                    and len(np_leaves) == len(self._shadow)
                    and all(a.shape == b.shape and a.dtype == b.dtype
                            for a, b in zip(np_leaves, self._shadow))):
                kind, base_step = "delta", self._shadow_step
            tmp = tempfile.mkdtemp(dir=self.dir, prefix=".tmp_")
            try:
                arrays: Dict[str, np.ndarray] = {}
                dtypes: Dict[str, str] = {}
                for i, a in enumerate(np_leaves):
                    if kind == "delta" and a.ndim >= 1:
                        idx = diff_leading_rows(self._shadow[i], a)
                        val, raw = _raw_view(a[idx])
                        if raw is not None:
                            dtypes[f"leaf_{i}"] = raw
                        arrays[f"leaf_{i}_idx"] = idx
                        arrays[f"leaf_{i}_val"] = val
                    else:   # full leaf; 0-d leaves are always written whole
                        whole, raw = _raw_view(a)
                        if raw is not None:
                            dtypes[f"leaf_{i}"] = raw
                        arrays[f"leaf_{i}"] = whole
                blob, cinfo = _codec().encode_payload(
                    arrays, codec=self.codec, fp_lanes=())
                with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                manifest = {
                    "step": step,
                    "kind": kind,
                    "base_step": base_step,
                    "n_leaves": len(leaves),
                    "raw_dtypes": dtypes,
                    "sha256": hashlib.sha256(blob).hexdigest(),
                    "nbytes": len(blob),
                    "codec": cinfo["codec"],
                    "raw_sha256": cinfo.get("raw_sha256"),
                    "raw_nbytes": cinfo.get("raw_nbytes"),
                    "time": time.time(),
                    "meta": meta or {},
                }
                with open(os.path.join(tmp, "MANIFEST.json"), "w") as f:
                    json.dump(manifest, f)
                    f.flush()
                    os.fsync(f.fileno())
                final = self._step_dir(step)
                if os.path.exists(final):
                    shutil.rmtree(final)
                os.rename(tmp, final)
            except Exception:
                shutil.rmtree(tmp, ignore_errors=True)
                raise
            # the shadow must hold the as-saved CONTENT: np.asarray of a
            # numpy leaf aliases the caller's live buffer (an in-place
            # mutation before the next save would diff the array against
            # itself and silently record an empty delta) — copy those; jax
            # buffers are immutable and safe to hold by reference.
            self._shadow = [a if isinstance(x, jax.Array) else np.array(a)
                            for x, a in zip(leaves, np_leaves)]
            self._shadow_step = step
            self._since_full = 0 if kind == "full" else self._since_full + 1
            self.last_save_kind, self.last_save_bytes = kind, len(blob)
            self._gc()
            obs.count("persist.bytes", len(blob))
            return self._step_dir(step)

    # -- chain-walk loading --
    def _verified_arrays(self, step: int, manifest: Dict
                         ) -> Optional[Dict[str, np.ndarray]]:
        """Load + sha256-verify one step's arrays.npz; None when torn."""
        path = os.path.join(self._step_dir(step), "arrays.npz")
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        want = manifest.get("sha256")
        if want is not None and hashlib.sha256(blob).hexdigest() != want:
            return None
        try:
            # decodes compressed containers and legacy raw npz alike; a
            # CodecError (torn container / failed raw_sha256) means torn
            payload, _info = _codec().decode_payload(blob)
            return payload
        except Exception:   # noqa: BLE001 — short/garbled blob
            return None

    def _collect_chain(self, step: int) -> Optional[List[Tuple[int, Dict,
                                                               Dict]]]:
        """Walk ``step`` back to its base full, verifying every member.
        Returns [(step, manifest, arrays), ...] full-first, or None the
        moment any link is missing/torn/corrupt (caller falls back)."""
        chain: List[Tuple[int, Dict, Dict]] = []
        s: Optional[int] = step
        seen = set()
        while True:
            if s is None or s in seen:
                return None        # dangling or cyclic base pointer
            seen.add(s)
            try:
                man = self.manifest(s)
            except (OSError, json.JSONDecodeError):
                return None
            arrs = self._verified_arrays(s, man)
            if arrs is None:
                return None
            chain.append((s, man, arrs))
            if man.get("kind", "full") == "full":
                chain.reverse()
                return chain
            s = man.get("base_step")

    def load_arrays(self, step: Optional[int] = None
                    ) -> Tuple[Dict[str, np.ndarray], Dict, int]:
        """Chain-walk load with torn/corrupt-delta fallback.

        Returns ``(arrays, manifest, restored_step)`` where ``arrays`` is
        the composed ``leaf_{i}`` dict (full + deltas applied oldest-first)
        and ``manifest`` belongs to ``restored_step``. When the requested
        step's chain is broken, falls back to the newest *intact full* at
        ``step <= requested`` (recorded in ``self.last_restore``); raises
        ``FileNotFoundError`` only when nothing verifies.
        """
        requested = step if step is not None else self.latest_step()
        if requested is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        self.last_restore = {"requested": requested, "restored": None,
                             "chain_len": 0, "fell_back": False}
        chain = self._collect_chain(requested)
        if chain is None:
            # fallback: newest verifiable full at or before the request.
            self.last_restore["fell_back"] = True
            for s in reversed([x for x in self.steps() if x <= requested]):
                try:
                    man = self.manifest(s)
                except (OSError, json.JSONDecodeError):
                    continue
                if man.get("kind", "full") != "full":
                    continue
                arrs = self._verified_arrays(s, man)
                if arrs is not None:
                    chain = [(s, man, arrs)]
                    break
            if chain is None:
                raise FileNotFoundError(
                    f"snapshot chain for step {requested} is torn and no "
                    f"intact full snapshot <= {requested} exists in "
                    f"{self.dir}")
        base_step, base_man, arrays = chain[0]
        n_leaves = base_man.get("n_leaves", 0)
        for s, man, delta in chain[1:]:
            for i in range(n_leaves):
                if f"leaf_{i}" in delta:      # 0-d / whole-leaf record
                    arrays[f"leaf_{i}"] = delta[f"leaf_{i}"]
                else:
                    arrays[f"leaf_{i}"] = apply_row_delta(
                        arrays[f"leaf_{i}"], delta[f"leaf_{i}_idx"],
                        delta[f"leaf_{i}_val"])
        top_step, top_man, _ = chain[-1]
        self.last_restore.update({"restored": top_step,
                                  "chain_len": len(chain)})
        return arrays, top_man, top_step

    def restore(self, template: Any, step: Optional[int] = None
                ) -> Tuple[Any, int]:
        """Restore into the dtype/placement of ``template``.

        Walks the delta chain (see ``load_arrays``); the returned step is
        the *actually restored* one — older than requested when a torn or
        corrupt chain member forced the fallback to the newest intact full
        (the caller then replays a longer log tail).
        """
        import ml_dtypes  # noqa: F401  (dtype registry for raw views)
        arrays, manifest, step = self.load_arrays(step)
        raw_dtypes = manifest.get("raw_dtypes", {})
        leaves, treedef = jax.tree.flatten(template)
        n_saved = manifest.get("n_leaves", len(leaves))
        if n_saved != len(leaves):
            raise ValueError(
                f"checkpoint step {step} holds {n_saved} leaves but the "
                f"restore template has {len(leaves)} — engine config / "
                f"store layout mismatch (e.g. hash vs region cooc)?")
        new = []
        for i, leaf in enumerate(leaves):
            a = arrays[f"leaf_{i}"]
            if f"leaf_{i}" in raw_dtypes:
                a = a.view(np.dtype(raw_dtypes[f"leaf_{i}"]))
            new.append(jax.numpy.asarray(
                a, leaf.dtype if hasattr(leaf, "dtype") else None))
        return jax.tree.unflatten(treedef, new), step

    def restore_host(self, step: Optional[int] = None) -> Dict[str, np.ndarray]:
        arrays, _, _ = self.load_arrays(step)
        return arrays

    def _gc(self) -> None:
        steps = self.steps()
        keep = set(steps) if self.keep_n <= 0 else set(steps[-self.keep_n:])
        # chain protection: a kept delta pins its whole base chain — a full
        # is never unlinked while a retained delta still references it.
        for s in list(keep):
            cur = s
            for _ in range(len(steps) + 1):
                try:
                    man = self.manifest(cur)
                except (OSError, json.JSONDecodeError):
                    break
                if man.get("kind", "full") == "full":
                    break
                base = man.get("base_step")
                if base is None or base == cur:
                    break
                keep.add(base)
                cur = base
        for s in steps:
            if s not in keep:
                shutil.rmtree(self._step_dir(s), ignore_errors=True)
        # stale ``.tmp_*`` dirs left by crashed writers: a successful save
        # renames its tmp dir away, a failed one rmtree's it — anything
        # still here past the TTL belongs to a dead process.
        now = time.time()
        for name in os.listdir(self.dir):
            if not name.startswith(".tmp"):
                continue
            path = os.path.join(self.dir, name)
            try:
                age = now - os.path.getmtime(path)
            except OSError:
                continue
            if age >= self.tmp_ttl_s:
                shutil.rmtree(path, ignore_errors=True)


def corrupt_snapshot(ckpt: CheckpointManager, step: int,
                     keep_fraction: float = 0.5) -> None:
    """Failure injection: truncate a snapshot's ``arrays.npz`` in place (a
    torn write on a non-atomic filesystem). The chain walk's sha256 pass
    must reject it and fall back to the newest intact full snapshot."""
    path = os.path.join(ckpt._step_dir(step), "arrays.npz")
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(blob[: max(1, int(len(blob) * keep_fraction))])


# ---------------------------------------------------------------------------
# Leader election + replica group (paper §4.2 persistence pattern)
# ---------------------------------------------------------------------------

def elect_leader(live_replicas: Iterable[int]) -> Optional[int]:
    """Deterministic single-writer election: lowest live replica id."""
    live = sorted(live_replicas)
    return live[0] if live else None


class ReplicaGroup:
    """Replicated backend instances with single-writer persistence.

    Every replica holds the full engine state (the paper's replicated-not-
    sharded backend); each persistence cycle, the elected leader writes.
    ``fail``/``recover`` drive failure injection in tests; a recovered
    replica cold-starts from the latest checkpoint (paper: "upon a cold
    restart, the frontend caches can serve the most recently persisted
    results immediately").
    """

    def __init__(self, n_replicas: int, ckpt: CheckpointManager):
        self.alive = {i: True for i in range(n_replicas)}
        self.ckpt = ckpt
        # leadership epoch: bumped on EVERY leadership change (fail of the
        # leader, or a lower-id replica rejoining and re-winning the
        # deterministic election). The fencing token for the shared log:
        # the winner stamps it into the log manifest
        # (``FirehoseLogWriter.assume_epoch``) before its first append, so
        # a zombie ex-leader's stray appends are rejected.
        self.epoch = 0
        self._last_leader = self.leader()

    def live(self) -> List[int]:
        return [i for i, ok in self.alive.items() if ok]

    def leader(self) -> Optional[int]:
        return elect_leader(self.live())

    def _note_leadership(self) -> Optional[int]:
        lead = self.leader()
        if lead != self._last_leader:
            self.epoch += 1
            self._last_leader = lead
        return lead

    def fail(self, rid: int) -> None:
        self.alive[rid] = False
        self._note_leadership()

    def recover(self, rid: int) -> Optional[int]:
        """Rejoin; returns the checkpoint step to cold-start from.

        Rejoining may retake leadership (lowest live id wins) — that too is
        a leadership change and bumps the epoch, so the previous leader's
        writer is fenced the moment the rejoiner stamps the manifest."""
        self.alive[rid] = True
        self._note_leadership()
        return self.ckpt.latest_step()

    def persist(self, rid: int, step: int, tree: Any,
                meta: Optional[Dict] = None) -> bool:
        """Only the leader's write goes through (single-writer)."""
        if rid != self.leader():
            return False
        self.ckpt.save(step, tree, meta)
        return True

    def log_append(self, rid: int, writer: Any, *args, **kwargs) -> bool:
        """Leader-elected single WRITER for the durable firehose log.

        Every replica consumes the hoses (paper §4.2: replicated, not
        sharded), but only the elected leader appends to the shared durable
        log — the same single-writer pattern as ``persist``. Non-leader
        appends are dropped (return False); on failover the new leader's
        appends continue the log seamlessly because ticks, not writers,
        define the offset space, and a (possibly long-standby) writer
        re-syncs its manifest view at every segment start.

        Election alone cannot stop a partitioned/paused ex-leader that
        still believes it leads — that is what the epoch fence is for: the
        new leader calls ``writer.assume_epoch(group.epoch)`` before its
        first append, and the zombie's next append/flush raises
        ``streaming.log.WriterFencedError`` (see ``distributed.fleet`` for
        the full failover choreography).
        """
        if rid != self.leader():
            return False
        writer.append(*args, **kwargs)
        return True


# ---------------------------------------------------------------------------
# Straggler mitigation notes (mechanisms live where the work happens):
#  * fixed-size micro-batching (core/engine.py) — per-step work is constant,
#    the Zipf skew that stretched the paper's reduce tasks cannot stretch a
#    device step;
#  * pair salting (core/sharded_engine.py) — each source's pairs are
#    spread over several shards, bounding a heavy hitter's per-shard
#    update volume;
#  * capacity-bounded routing/dispatch (sharded engine buckets, MoE
#    capacity) — a skewed key/expert cannot inflate a neighbor's step time,
#    overflow is dropped and counted instead of straggling.
# ---------------------------------------------------------------------------
