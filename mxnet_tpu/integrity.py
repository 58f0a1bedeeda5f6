"""Integrity plane: silent-data-corruption detection, cross-replica
state attestation, and corrupt-rank quarantine (ISSUE 16).

Every failure the resilience stack survives is *loud* — a dead
heartbeat (ElasticGang), a NaN gradient (numerics.StepGuard), a torn
file (checkpoint CRCs).  The dominant unhandled hazard at fleet scale
is *silent* corruption: a flipped bit in a parameter shard, a
defective core producing subtly wrong math, a replica whose state has
drifted — every rank keeps reporting "healthy" while training
diverges.  The whole-program capture discipline (gluon/captured.py)
makes cheap detection possible: dp replicas of a captured step are
bitwise-identical by construction, so ANY cross-replica fingerprint
mismatch is corruption by definition, and a deterministically
re-executed step is a free ground-truth oracle.

Three detection tiers, riding entirely on existing substrates:

- **Tier 1 — cross-replica attestation** (`IntegrityPlane.attest`):
  every ``MXTPU_INTEGRITY_EVERY`` (default 50) steps each rank
  publishes a fingerprint of its full parameter+optimizer-state pytree
  at ``integrity/<epoch>/<step>/<rank>`` on the gang KV (the channel
  heartbeats already ride).  The fingerprint is computed *inside* the
  captured step (`fingerprint_arrays` as an extra program output gated
  by a traced ``attest`` predicate — zero extra dispatches) and read
  back with the existing StepGuard readback.  Replicas that must be
  bitwise-equal vote: the majority value is truth, the minority
  rank(s) are corrupt.

- **Tier 2 — shadow replay audit** (`IntegrityPlane.retain` /
  ``audit``): re-execute the last attested step from the retained
  pre-step snapshot through the same step function and compare
  fingerprints.  Works at world size 1, and *classifies* the
  corruption: replay disagreeing with the live result means the live
  state was mutated after the fact (``kind="memory"``, e.g. a bit
  flip); replay agreeing with itself while peers disagree means the
  math itself is wrong deterministically (``kind="compute"``, a bad
  core).

- **Tier 3 — lineage ledger** (`IntegrityLedger`): each attestation is
  hash-chained onto the previous one in a per-run JSONL ledger (next
  to the autotune tuning DB).  `checkpoint.AsyncCheckpointer` stamps
  the ledger head into MANIFEST.json and restore verifies provenance
  (`verify_provenance`) — a checkpoint audits back to its origin, not
  just its transport CRCs.

On confirmed corruption the plane emits ``sdc_detected{rank, kind,
step}``, and `quarantine` turns the verdict into a
`resilience.RankFailure` so the existing ElasticGang evict/amendment
path reshapes the gang, restores the corrupt rank's state from a buddy
snapshot or the manifest, and grows back.

Fingerprint math: every leaf is reinterpreted as uint32 words and
folded as ``sum(word[i] * (2*i+1) * salt(leaf))`` into two mod-2^32
accumulators with independent per-leaf salts.  All weights are odd,
hence invertible mod 2^32, so any single-bit flip in any word changes
the sum; modular addition is exact and associative, so the jitted
device reduction (`fingerprint_arrays`) and the numpy host mirror
(`fingerprint_host`) agree bitwise regardless of reduction order —
pinned by tests/test_integrity.py.

Env knobs (docs/env_vars.md): ``MXTPU_INTEGRITY`` (default off),
``MXTPU_INTEGRITY_EVERY`` (50), ``MXTPU_INTEGRITY_LEDGER`` (ledger
path override), ``MXTPU_INTEGRITY_TIMEOUT`` (peer-wait seconds, 5).
Fault sites (docs/resilience.md): ``bit_flip_param:K`` /
``bit_flip_grad:K`` (flip one bit on rank K) and ``bad_core:K``
(rank K computes a deterministically wrong answer).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time

try:
    from .base import MXNetError
except ImportError:     # standalone load (tools)
    MXNetError = RuntimeError

_SALT_LO = 0x9E3779B1   # odd golden-ratio constants: per-leaf salts
_SALT_HI = 0x85EBCA77   # stay odd (odd * odd), hence invertible
_MASK32 = 0xFFFFFFFF


# -- env plumbing --------------------------------------------------------------

def enabled() -> bool:
    """MXTPU_INTEGRITY gate (default off): when on, the captured step
    computes the state fingerprint in-program and the Trainer attests
    on the plane attached via ``Trainer.attach_integrity``."""
    return os.environ.get("MXTPU_INTEGRITY", "").lower() \
        in ("1", "true", "on", "yes")


def fingerprint_enabled() -> bool:
    """Alias read by `gluon.captured.get_step` — the flag joins the
    capture cache key (a toggled value must re-trace: the program
    grows/loses the fingerprint output)."""
    return enabled()


def attest_every(default=50) -> int:
    """MXTPU_INTEGRITY_EVERY: attestation period in steps."""
    try:
        v = int(os.environ.get("MXTPU_INTEGRITY_EVERY", default))
    except ValueError:
        v = default
    return max(1, v)


def peer_timeout(default=5.0) -> float:
    """MXTPU_INTEGRITY_TIMEOUT: how long `attest` waits for layout-mate
    fingerprints before voting on what arrived."""
    try:
        v = float(os.environ.get("MXTPU_INTEGRITY_TIMEOUT", default))
    except ValueError:
        v = default
    return max(0.0, v)


def ledger_path():
    """Ledger location: MXTPU_INTEGRITY_LEDGER when set, else
    ``integrity_ledger.jsonl`` next to the autotune tuning DB
    (MXTPU_TUNE_DB's directory), else None (ledger off — attestation
    still works, provenance stamping degrades)."""
    p = os.environ.get("MXTPU_INTEGRITY_LEDGER")
    if p:
        return p
    db = os.environ.get("MXTPU_TUNE_DB")
    if db:
        return os.path.join(os.path.dirname(db) or ".",
                            "integrity_ledger.jsonl")
    return None


def self_rank(default=0) -> int:
    """This process's fleet rank (MXTPU_WORKER_RANK, the launch.py
    identity every other subsystem keys on) — what the rank-targeted
    SDC fault sites compare against when no gang rank is supplied."""
    try:
        return int(os.environ.get("MXTPU_WORKER_RANK", default))
    except ValueError:
        return default


def _tel_event(name, /, **fields):
    """Import-guarded telemetry event (this module also loads
    standalone, e.g. from tools/).  The event name is positional-only
    so a ``kind`` detail field passes through cleanly."""
    try:
        from . import telemetry
    except ImportError:
        return
    telemetry.event(name, **fields)


def _tel_integrity(**fields):
    try:
        from . import telemetry
    except ImportError:
        return
    telemetry.integrity_record(**fields)


# -- fingerprint math ----------------------------------------------------------

def _salts(j):
    lo = (_SALT_LO * (2 * j + 1)) & _MASK32
    hi = (_SALT_HI * (2 * j + 1)) & _MASK32
    return lo, hi


def fingerprint_arrays(arrs):
    """Pure, traceable fingerprint reduction over arrays → ``(2,)``
    uint32 ``[lo, hi]``.  The ONE home of the device-side math: the
    whole-step capture inlines it as an extra program output, so the
    fingerprint costs zero extra dispatches.  Per leaf ``j``, words are
    weighted ``(2*i+1) * salt_j`` (odd → any single-bit flip changes
    the sum mod 2^32); the iota fuses into the reduction, nothing is
    materialized."""
    import jax
    import jax.numpy as jnp

    lo = jnp.zeros((), jnp.uint32)
    hi = jnp.zeros((), jnp.uint32)
    for j, a in enumerate(arrs):
        r = jnp.asarray(a)
        if r.size == 0:
            continue
        w = _device_words(r)
        idx = jax.lax.iota(jnp.uint32, w.size)
        base = w * (idx * jnp.uint32(2) + jnp.uint32(1))
        slo, shi = _salts(j)
        lo = lo + jnp.sum(base * jnp.uint32(slo), dtype=jnp.uint32)
        hi = hi + jnp.sum(base * jnp.uint32(shi), dtype=jnp.uint32)
    return jnp.stack([lo, hi])


def _device_words(r):
    """Reinterpret one device array as a flat uint32 word vector."""
    import jax.numpy as jnp
    from jax import lax

    if r.dtype == jnp.bool_:
        return r.astype(jnp.uint32).reshape(-1)
    size = jnp.dtype(r.dtype).itemsize
    if size == 4:
        return lax.bitcast_convert_type(r, jnp.uint32).reshape(-1)
    if size == 2:
        return lax.bitcast_convert_type(r, jnp.uint16) \
            .astype(jnp.uint32).reshape(-1)
    if size == 1:
        return lax.bitcast_convert_type(r, jnp.uint8) \
            .astype(jnp.uint32).reshape(-1)
    # 8-byte leaves: bitcast appends a trailing word dim (low word
    # first on little-endian hosts, matching the numpy mirror)
    return lax.bitcast_convert_type(r, jnp.uint32).reshape(-1)


def fingerprint_pytree(tree):
    """`fingerprint_arrays` over ``jax.tree_util.tree_leaves(tree)``."""
    import jax

    return fingerprint_arrays(jax.tree_util.tree_leaves(tree))


def _host_words(a):
    import numpy as np

    a = np.ascontiguousarray(a)
    if a.dtype == np.bool_:
        return a.astype(np.uint32).ravel()
    size = a.dtype.itemsize
    if size == 4:
        return a.view(np.uint32).ravel()
    if size == 2:
        return a.view(np.uint16).ravel().astype(np.uint32)
    if size == 1:
        return a.view(np.uint8).ravel().astype(np.uint32)
    if size % 4 == 0:
        return a.view(np.uint32).ravel()
    return a.astype(np.float32).view(np.uint32).ravel()


def fingerprint_host(tree) -> int:
    """Numpy mirror of `fingerprint_pytree`, already combined into one
    u64 int — bitwise-identical to `combine(device_fp)` for the same
    leaves (same weights, and mod-2^32 addition is order-free)."""
    import numpy as np

    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except ImportError:
        leaves = _py_leaves(tree)
    lo = hi = 0
    for j, a in enumerate(leaves):
        a = np.asarray(a)
        if a.size == 0:
            continue
        w = _host_words(a).astype(np.uint64)
        idx = np.arange(w.size, dtype=np.uint64)
        base = (w * (idx * 2 + 1)) & _MASK32
        slo, shi = _salts(j)
        lo = (lo + int(np.sum((base * slo) & _MASK32) & _MASK32)) \
            & _MASK32
        hi = (hi + int(np.sum((base * shi) & _MASK32) & _MASK32)) \
            & _MASK32
    return (hi << 32) | lo


def _py_leaves(tree):
    """Deterministic jax-free leaf flattening (dicts by sorted key) for
    standalone consumers; matches tree_leaves for the list/tuple/dict
    pytrees the numpy gang tests use."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out.extend(_py_leaves(tree[k]))
        return out
    if isinstance(tree, (list, tuple)):
        out = []
        for v in tree:
            out.extend(_py_leaves(v))
        return out
    return [tree]


def combine(fp2) -> int:
    """Fold a host-read ``(2,)`` uint32 fingerprint into one u64."""
    import numpy as np

    v = np.asarray(fp2)
    return (int(v[1]) << 32) | int(v[0])


def fp_hex(fp: int) -> str:
    return f"{int(fp):016x}"


# NOTE: the host mirror must wrap ``base`` to 32 bits BEFORE the salt
# multiply — the device computes base = w * (2i+1) IN uint32, so the
# wrap happens there implicitly.  (w*(2i+1)) mod 2^32 then *salt mod
# 2^32 equals the device's uint32 chain because products mod 2^32
# compose.


# -- lineage ledger (tier 3) ---------------------------------------------------

_GENESIS = "0" * 64


class IntegrityLedger:
    """Hash-chained JSONL attestation ledger.

    Each line: ``{"step", "epoch", "rank", "fp", "prev", "hash", "t",
    "run"}`` where ``hash = sha256(prev + canonical-json(entry sans
    hash))``.  `head()` is the newest hash — `AsyncCheckpointer` stamps
    it into MANIFEST.json so `verify_provenance` can audit a restored
    checkpoint back to an attestation this process actually chained.
    Appends are serialized and fsync'd line-at-a-time (same durability
    discipline as the telemetry sink)."""

    def __init__(self, path):
        self.path = path
        self._lock = threading.Lock()
        self._head = None

    def head(self):
        """Newest chain hash, or None on an empty/absent ledger."""
        with self._lock:
            if self._head is None:
                entries = self.entries()
                self._head = entries[-1]["hash"] if entries else None
            return self._head

    def entries(self):
        """All parseable ledger lines, oldest first (torn tail lines
        are skipped, never fatal)."""
        if not self.path or not os.path.exists(self.path):
            return []
        out = []
        with open(self.path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and rec.get("hash"):
                    out.append(rec)
        return out

    @staticmethod
    def _entry_hash(prev, body):
        payload = json.dumps(body, sort_keys=True,
                             separators=(",", ":"))
        return hashlib.sha256(
            (prev + payload).encode("utf-8")).hexdigest()

    def append(self, step, fp, rank=0, epoch=0, run=None):
        """Chain one attestation; returns the entry (with its hash)."""
        if not self.path:
            return None
        with self._lock:
            prev = self._head
            if prev is None:
                entries = self.entries()
                prev = entries[-1]["hash"] if entries else _GENESIS
            body = {"step": int(step), "epoch": int(epoch),
                    "rank": int(rank), "fp": fp_hex(fp),
                    "prev": prev, "t": time.time()}
            if run is not None:
                body["run"] = run
            entry = dict(body, hash=self._entry_hash(prev, body))
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(self.path, "a", encoding="utf-8") as f:
                f.write(json.dumps(entry, sort_keys=True,
                                   separators=(",", ":")) + "\n")
                f.flush()
                os.fsync(f.fileno())
            self._head = entry["hash"]
            return entry

    def verify_chain(self):
        """Recompute every hash link; returns (ok, reason)."""
        prev = _GENESIS
        for i, entry in enumerate(self.entries()):
            body = {k: v for k, v in entry.items() if k != "hash"}
            if body.get("prev") != prev:
                return False, f"entry {i}: prev {body.get('prev')!r} " \
                              f"does not chain onto {prev!r}"
            if self._entry_hash(prev, body) != entry["hash"]:
                return False, f"entry {i}: hash mismatch (ledger " \
                              f"tampered or torn mid-line)"
            prev = entry["hash"]
        return True, None

    def has_hash(self, h):
        if not h:
            return False
        return any(e.get("hash") == h for e in self.entries())


_LEDGER = None
_LEDGER_LOCK = threading.Lock()


def get_ledger():
    """Process-wide ledger for the current `ledger_path()` (None when
    no path resolves)."""
    global _LEDGER
    path = ledger_path()
    if path is None:
        return None
    with _LEDGER_LOCK:
        if _LEDGER is None or _LEDGER.path != path:
            _LEDGER = IntegrityLedger(path)
        return _LEDGER


def reset():
    """Drop the cached ledger handle (test isolation)."""
    global _LEDGER
    with _LEDGER_LOCK:
        _LEDGER = None


def ledger_head():
    """Current chain head for manifest stamping, or None."""
    led = get_ledger()
    return None if led is None else led.head()


def manifest_stamp():
    """The ``integrity`` block `checkpoint._write_manifest` embeds, or
    None when no ledger is configured / nothing attested yet."""
    led = get_ledger()
    if led is None:
        return None
    head = led.head()
    if head is None:
        return None
    return {"ledger_head": head, "ledger_path": led.path}


def verify_provenance(manifest):
    """Audit a manifest's integrity stamp against the local ledger.

    Returns (ok, reason).  Lenient where it must be — an unstamped
    manifest (pre-integrity writer) or an absent ledger (fresh machine,
    checkpoint shipped in) passes with a reason string — but a stamp
    that names a hash the ledger does NOT contain fails closed: the
    checkpoint claims a lineage this host has no record of."""
    stamp = manifest.get("integrity") if isinstance(manifest, dict) \
        else None
    if not isinstance(stamp, dict) or not stamp.get("ledger_head"):
        return True, "manifest carries no integrity stamp"
    led = get_ledger()
    if led is None or not os.path.exists(led.path or ""):
        return True, "no local ledger to audit against"
    ok, reason = led.verify_chain()
    if not ok:
        return False, f"ledger chain invalid: {reason}"
    if not led.has_hash(stamp["ledger_head"]):
        return False, (f"manifest ledger head "
                       f"{stamp['ledger_head'][:12]}... not present in "
                       f"{led.path}")
    return True, None


# -- tier 1 + 2: the plane -----------------------------------------------------

class IntegrityPlane:
    """Per-rank attestation driver.

    ``kv``: gang KV (FileKV/TcpKV — `distributed.gang_kv()` by
    default; None degrades to solo mode where only the ledger and the
    replay audit operate).  ``peers``: the ranks whose state must be
    bitwise-equal to ours (dp replicas; tp/fsdp shards pass their
    layout-mates).  Default: all of ``range(world)``."""

    def __init__(self, rank=0, world=1, kv=None, peers=None, every=None,
                 epoch=0, ledger=None, timeout=None, run=None):
        self.rank = int(rank)
        self.world = int(world)
        self.kv = kv
        self.peers = sorted(set(int(r) for r in peers)) \
            if peers is not None else list(range(self.world))
        if self.rank not in self.peers:
            self.peers = sorted(self.peers + [self.rank])
        self.every = attest_every() if every is None else max(1, int(every))
        self.epoch = int(epoch)
        self.timeout = peer_timeout() if timeout is None else float(timeout)
        self.ledger = get_ledger() if ledger is None else ledger
        self.run = run
        self.attestations = 0
        self.mismatches = 0
        self.replays = 0
        self.last_verdict = None
        self._retained = {}          # step -> (state, inputs)

    # -- schedule ---------------------------------------------------------------

    def due(self, step) -> bool:
        return step is not None and int(step) % self.every == 0

    # -- tier 2 retention -------------------------------------------------------

    def retain(self, step, state, inputs=None):
        """Retain the PRE-step state (host copies) + the step's inputs
        for shadow replay.  Bounded to the most recent retention — the
        audit only ever replays the last attested step."""
        self._retained = {int(step): (state, inputs)}

    def retained(self, step=None):
        if step is not None:
            return self._retained.get(int(step))
        if not self._retained:
            return None
        s = max(self._retained)
        return (s,) + self._retained[s]

    # -- tier 1 attestation -----------------------------------------------------

    def _key(self, epoch, step, rank):
        return f"integrity/{epoch}/{step}/{rank}"

    def publish(self, step, fp, epoch=None):
        epoch = self.epoch if epoch is None else int(epoch)
        if self.kv is not None:
            self.kv.put_json(self._key(epoch, step, self.rank), {
                "rank": self.rank, "step": int(step), "epoch": epoch,
                "fp": fp_hex(fp), "t": time.time()})
        if self.ledger is not None:
            self.ledger.append(step, fp, rank=self.rank, epoch=epoch,
                               run=self.run)

    def _gather(self, step, epoch):
        """Poll the KV until every peer published (or timeout):
        {rank: fp_hex}."""
        got = {}
        want = [r for r in self.peers]
        deadline = time.monotonic() + self.timeout
        while True:
            for r in want:
                if r in got:
                    continue
                try:
                    rec = self.kv.get_json(self._key(epoch, step, r))
                except Exception:
                    rec = None
                if isinstance(rec, dict) and rec.get("fp"):
                    got[r] = rec["fp"]
            if len(got) == len(want) or time.monotonic() >= deadline:
                return got
            time.sleep(0.005)

    def attest(self, step, fp, epoch=None):
        """One attestation round: publish, gather layout-mates, vote.

        Returns the verdict dict ``{step, epoch, fp, ok, corrupt,
        tie, votes, self_corrupt, absent}``.  Majority is truth; the
        minority rank(s) are corrupt.  A two-way tie (possible only
        with an even quorum) is reported ``ok=False, tie=True`` with
        no rank named — the replay audit is the tie-breaker.  Emits
        one ``integrity`` telemetry record per round; on a mismatch
        the lowest healthy voter additionally emits
        ``integrity_mismatch`` and one ``sdc_detected`` per corrupt
        rank (kind refined later by `audit`)."""
        step = int(step)
        epoch = self.epoch if epoch is None else int(epoch)
        self.attestations += 1
        self.publish(step, fp, epoch=epoch)
        mine = fp_hex(fp)
        votes = {self.rank: mine}
        if self.kv is not None and len(self.peers) > 1:
            votes.update(self._gather(step, epoch))
        tally = {}
        for r, v in votes.items():
            tally.setdefault(v, []).append(r)
        ranked = sorted(tally.items(),
                        key=lambda kv_: (-len(kv_[1]), min(kv_[1])))
        best_fp, best_ranks = ranked[0]
        tie = len(ranked) > 1 and len(ranked[1][1]) == len(best_ranks)
        ok = len(ranked) == 1
        corrupt = [] if ok or tie else sorted(
            r for v, rs in ranked[1:] for r in rs)
        absent = sorted(set(self.peers) - set(votes))
        verdict = {
            "step": step, "epoch": epoch, "fp": mine, "ok": ok,
            "tie": tie, "corrupt": corrupt, "votes": votes,
            "absent": absent, "self_corrupt": self.rank in corrupt,
        }
        self.last_verdict = verdict
        if not ok:
            self.mismatches += 1
        _tel_integrity(step=step, fp=mine, ok=ok, epoch=epoch,
                       peers=len(votes), corrupt=corrupt or None,
                       rank=self.rank)
        healthy = tally.get(best_fp, [])
        if not ok and not tie and healthy and \
                self.rank == min(healthy):
            # one announcer per verdict (the amendment discipline:
            # lowest healthy member speaks for the quorum)
            _tel_event("integrity_mismatch", step=step, epoch=epoch,
                       corrupt=corrupt, votes=len(votes))
            for r in corrupt:
                _tel_event("sdc_detected", rank=r, step=step,
                           kind="state_mismatch", epoch=epoch)
        return verdict

    # -- tier 2 audit -----------------------------------------------------------

    def audit(self, step_fn, live_fp, step=None, peers_agree=None):
        """Shadow replay: re-run the retained pre-step snapshot through
        ``step_fn(state, inputs) -> new_state`` and fingerprint the
        result (host math — `fingerprint_host`).

        Classification:
        - replay != live  → ``"memory"``: the live state was mutated
          outside the computation (bit flip / corrupt HBM);
        - replay == live, peers disagree → ``"compute"``: the step
          deterministically produces a wrong answer (bad core);
        - replay == live, peers agree (or solo) → ``"clean"``.

        Emits a ``replay_audit`` event, plus a kind-refined
        ``sdc_detected`` when corruption is confirmed.  Returns
        ``{kind, replay_fp, live_fp, step}`` or None when nothing is
        retained for the step."""
        if peers_agree is None:
            v = self.last_verdict
            peers_agree = v is None or v["ok"] or \
                self.rank not in v.get("corrupt", ())
        if step is None:
            ret = self.retained()
            if ret is None:
                return None
            step, state, inputs = ret
        else:
            ret = self.retained(step)
            if ret is None:
                return None
            state, inputs = ret
        self.replays += 1
        new_state = step_fn(state, inputs) if inputs is not None \
            else step_fn(state)
        replay_fp = fingerprint_host(new_state)
        live = int(live_fp)
        if replay_fp != live:
            kind = "memory"
        elif not peers_agree:
            kind = "compute"
        else:
            kind = "clean"
        out = {"kind": kind, "replay_fp": fp_hex(replay_fp),
               "live_fp": fp_hex(live), "step": int(step)}
        _tel_event("replay_audit", rank=self.rank, step=int(step),
                   kind=kind, replay_fp=out["replay_fp"],
                   live_fp=out["live_fp"])
        if kind != "clean":
            _tel_event("sdc_detected", rank=self.rank, step=int(step),
                       kind=kind, epoch=self.epoch)
        return out

    # -- quarantine -------------------------------------------------------------

    def quarantine(self, gang, verdict=None):
        """Turn a mismatch verdict into the `resilience.RankFailure`
        the existing elastic recovery path consumes: the survivors call
        ``gang.recover(failure)``, which reshapes the mesh around the
        corrupt rank(s) and restores state from a buddy snapshot or the
        disk manifest; the quarantined rank sees the epoch move past it
        (GangEvicted) and `ElasticGang.join`s back with clean state.
        Returns None when the verdict names nobody (ok or tie)."""
        from . import resilience

        verdict = self.last_verdict if verdict is None else verdict
        if not verdict or not verdict.get("corrupt"):
            return None
        corrupt = sorted(verdict["corrupt"])
        for r in corrupt:
            _tel_event("rank_quarantined", rank=r,
                       step=verdict.get("step"), epoch=gang.epoch)
        return resilience.RankFailure(corrupt, gang.epoch)


# -- fault-injection hooks (docs/resilience.md) --------------------------------

def _flip_bit_f32(raw, bit=20):
    """Flip one mantissa bit of element 0 of a float32 jax array."""
    import jax.numpy as jnp
    from jax import lax

    flat = raw.ravel()
    word = lax.bitcast_convert_type(flat[0], jnp.uint32)
    flipped = lax.bitcast_convert_type(
        word ^ jnp.uint32(1 << bit), raw.dtype)
    return flat.at[0].set(flipped).reshape(raw.shape)


def bit_flip_host(arr, bit=20):
    """In-place single-bit flip of element 0 of a numpy array (the
    thread-gang tests' corruption primitive)."""
    import numpy as np

    a = np.ascontiguousarray(arr)
    size = a.dtype.itemsize
    view = a.view({8: np.uint64, 4: np.uint32,
                   2: np.uint16}.get(size, np.uint8)).ravel()
    view[0] ^= type(view[0])(1 << min(bit, size * 8 - 1))
    if a is not arr:
        arr.ravel()[0] = a.ravel()[0]
    return arr


def maybe_bit_flip_param(rank=None, params=()) -> bool:
    """``bit_flip_param:K``: flip one bit in the first trainable
    parameter of rank K, once — the live state diverges from its
    replicas and from its own replay (``kind="memory"``).  Consumes
    the rank's charge; returns True when it fired."""
    from . import resilience

    if rank is None:
        rank = self_rank()
    if not resilience.consume_rank_fault("bit_flip_param", rank):
        return False
    for p in params:
        raw = getattr(getattr(p, "data", lambda: p)(), "_data", None)
        if raw is None:
            import numpy as np

            arr = np.asarray(p)
            if arr.dtype.kind != "f" or arr.size == 0:
                continue
            bit_flip_host(p if hasattr(p, "dtype") else arr)
            return True
        import jax.numpy as jnp

        if not jnp.issubdtype(raw.dtype, jnp.floating) or raw.size == 0:
            continue
        p.data()._set_data(_flip_bit_f32(raw))
        return True
    return False


def maybe_bit_flip_grad(rank=None, grads=()) -> bool:
    """``bit_flip_grad:K``: flip one bit in rank K's first float
    gradient before the update (eager path — the captured program's
    gradients never materialize, so the Trainer routes the armed step
    to the oracle, the ``nan_grad`` discipline)."""
    from . import resilience

    if rank is None:
        rank = self_rank()
    if not grads or not resilience.consume_rank_fault("bit_flip_grad",
                                                      rank):
        return False
    import jax.numpy as jnp

    for g in grads:
        raw = getattr(g, "_data", None)
        if raw is None or not jnp.issubdtype(raw.dtype, jnp.floating) \
                or raw.size == 0:
            continue
        g._set_data(_flip_bit_f32(raw))
        return True
    return False


def maybe_bad_core(rank=None, value=None):
    """``bad_core:K``: rank K's compute is deterministically wrong —
    returns a perturbed copy of ``value`` (the step's input) once the
    charge fires, else ``value`` unchanged.  Perturbing the INPUT
    before it is recorded for replay is what makes the shadow replay
    reproduce the wrong answer (replay == live, peers disagree →
    ``kind="compute"``)."""
    from . import resilience

    if rank is None:
        rank = self_rank()
    if not resilience.consume_rank_fault("bad_core", rank):
        return value
    import numpy as np

    out = np.array(value, copy=True)
    flat = out.ravel()
    if flat.size and out.dtype.kind == "f":
        flat[0] = flat[0] * 1.0000001 + 1e-6
    return out if isinstance(value, np.ndarray) else type(value)(out)
