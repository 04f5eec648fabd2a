"""Columnar batch-apply: the native group-commit mutation write path.

The serial write path (posting/mutation.apply_edges) builds Posting
objects per edge into txn.cache.deltas and serializes them per key at
commit (posting/pl.encode_deltas). That leaves tokenization, key
construction and record grouping as per-edge Python work under the GIL
— PR 11's own profiling pinned the residual mutation cost there.

This module collects the dominant edge shapes — scalar-value SET on a
non-list predicate (exact/int/bool/term indexes) and list-uid SET
(incl. @reverse) — into columnar arrays *instead of* postings. At
commit, a group-commit leader flattens every batch member's columns
into ONE native call (codec.cpp batch_apply) that fuses tokenization,
index/reverse key emission and delta-record encoding, returning
ready-to-put (key, record) pairs for a single kv.put_batch. Records
are byte-identical to the serial path's encode_delta output.

Correctness rules (all enforced here, fuzz-verified byte-for-byte in
tests/test_batch_apply.py):

  - ALL-OR-NOTHING PER TXN: columnar columns and Python deltas never
    coexist. Any ineligible edge (delete, lang, facets, rich
    tokenizer, live prior value needing deindex, ...) first
    *materializes* the collected columns back through the serial
    apply path, then proceeds serially — so delete-before-set
    ordering and the one-record-per-(key, commit_ts) MVCC invariant
    (MemKV overwrites same-ts puts) both survive.
  - In-txn reads materialize first: the engines' query/upsert entry
    points call txn.materialize_cols() before executing, so
    read-your-writes semantics are unchanged.
  - Conflict keys are computed at collect time in Python (the oracle
    needs them before the kernel runs); @upsert predicates with index
    tokenizers fall back (their conflict set includes index keys only
    the kernel would know).
  - Engines only enable collection when no commit-time consumer needs
    Posting objects (CDC, subscriptions, vector indexes); the commit
    entry re-checks and materializes if one appeared mid-txn.
"""

from __future__ import annotations

from array import array
from typing import Dict, List, Tuple

from dgraph_tpu.posting.pl import OP_SET
from dgraph_tpu.types.types import TypeID, convert, to_binary
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS
from dgraph_tpu.x import config, keys

# predicate tokenization plan bits (mirrored in codec.cpp batch_apply)
PF_REVERSE = 1
PF_EXACT = 2
PF_INT = 4
PF_BOOL = 8
PF_TERM = 16
_PF_TOKS = PF_EXACT | PF_INT | PF_BOOL | PF_TERM


def count_fallback(reason: str, n_edges: int) -> None:
    """One escape from the columnar path: aggregate + per-reason
    counters (the kernel-coverage regression signal)."""
    METRICS.inc("mutation_native_fallback_total", n_edges)
    METRICS.inc(
        f'mutation_native_fallback_total{{reason="{reason}"}}', n_edges
    )


class _Pred:
    """Per-(ns, attr) columnar plan: key prefix + tokenizer flag bits +
    identifier bytes, resolved once per predicate per txn (revalidated
    when the schema entry object changes mid-txn)."""

    __slots__ = (
        "su", "attr", "ns", "pid", "prefix", "flags", "idents",
        "upsert", "scalar_ok", "scalar_reason", "uid_ok", "uid_reason",
        "est_scalar", "est_uid",
    )

    def __init__(self, su, attr: str, ns: int, pid: int):
        from dgraph_tpu.tok.tok import (
            BoolTokenizer,
            ExactTokenizer,
            IntTokenizer,
            TermTokenizer,
        )

        self.su = su
        self.attr = attr
        self.ns = ns
        self.pid = pid
        self.prefix = keys.PredicatePrefix(attr, ns)
        self.upsert = bool(su.upsert)
        flags = 0
        idents = bytearray(4)
        scalar_ok, scalar_reason = True, ""
        uid_ok, uid_reason = True, ""
        if su.count:
            scalar_ok, scalar_reason = False, "count"
            uid_ok, uid_reason = False, "count"
        if su.is_uid:
            # a typed-value edge on a uid predicate is an error shape;
            # the serial path raises it with the right message
            scalar_ok, scalar_reason = False, "shape"
            if not su.is_list:
                # single-valued uid SET replaces the target (a read)
                uid_ok, uid_reason = False, "uid_single"
            if su.directive_reverse:
                flags |= PF_REVERSE
        else:
            uid_ok, uid_reason = False, "shape"
            if su.is_list:
                scalar_ok, scalar_reason = False, "list"
            else:
                for t in su.tokenizer_objs():
                    if (
                        isinstance(t, ExactTokenizer)
                        and su.value_type == TypeID.STRING
                    ):
                        flags |= PF_EXACT
                        idents[0] = t.identifier
                    elif (
                        isinstance(t, IntTokenizer)
                        and su.value_type == TypeID.INT
                    ):
                        flags |= PF_INT
                        idents[1] = t.identifier
                    elif (
                        isinstance(t, BoolTokenizer)
                        and su.value_type == TypeID.BOOL
                    ):
                        flags |= PF_BOOL
                        idents[2] = t.identifier
                    elif (
                        isinstance(t, TermTokenizer)
                        and su.value_type == TypeID.STRING
                    ):
                        flags |= PF_TERM
                        idents[3] = t.identifier
                    else:
                        # fulltext/trigram/hash/... or a tokenizer-type
                        # mismatch: the long tail stays Python
                        scalar_ok, scalar_reason = False, "tok"
                        break
                if scalar_ok and self.upsert and (flags & _PF_TOKS):
                    # @upsert conflicts on index keys — which only the
                    # kernel would produce, too late for the oracle
                    scalar_ok, scalar_reason = False, "upsert_index"
        self.flags = flags
        self.idents = bytes(idents)
        self.scalar_ok, self.scalar_reason = scalar_ok, scalar_reason
        self.uid_ok, self.uid_reason = uid_ok, uid_reason
        ntok = bin(flags & (PF_EXACT | PF_INT | PF_BOOL)).count("1")
        self.est_scalar = 1 + ntok + (2 if flags & PF_TERM else 0)
        self.est_uid = 1 + (1 if flags & PF_REVERSE else 0)


class ColumnarWriteSet:
    """Per-txn columnar collection of fast-shape edges (in place of
    txn.cache.deltas postings). Collection is all-or-nothing per
    apply_edges call; the original edges are retained so any later
    ineligible operation can replay them byte-identically through the
    serial path (materialize)."""

    __slots__ = (
        "shapes", "entities", "pids", "objects", "vtypes", "voffs",
        "vblob",
        "_preds", "pred_list", "_scalar_seen", "_chunks", "nposts_est",
    )

    def __init__(self):
        # columns are the cheap typed buffers native.batch_apply takes
        # by raw address — C-typed appends at collect, zero conversion
        # at the kernel call (the per-commit fixed cost is the enemy)
        self.shapes = bytearray()  # 0 scalar-value SET, 1 list-uid SET
        self.entities = array("Q")
        self.pids = array("i")
        self.objects = array("Q")  # uid-shape target (else 0)
        self.vtypes = bytearray()  # stored TypeID (scalar), else 0
        self.voffs = array("q", (0,))  # CSR offsets into vblob
        self.vblob = bytearray()  # to_binary bytes (scalar shapes)
        self._preds: Dict[Tuple[int, str], _Pred] = {}
        self.pred_list: List[_Pred] = []
        # scalar (ns, attr, entity) keys already collected: a second
        # write to a tokenized key needs the deindex read path
        self._scalar_seen: set = set()
        self._chunks: List[tuple] = []  # (st, edges, update_schema)
        self.nposts_est = 0

    @property
    def pending(self) -> bool:
        return bool(self._chunks)

    def _pred_for(self, su, attr: str, ns: int) -> _Pred:
        ck = (ns, attr)
        p = self._preds.get(ck)
        if p is not None and p.su is su:
            return p
        # new predicate — or the schema entry was replaced mid-txn:
        # already-collected edges keep their old plan under the old pid
        p = _Pred(su, attr, ns, len(self.pred_list))
        self._preds[ck] = p
        self.pred_list.append(p)
        return p

    def try_collect(self, txn, st, edges, update_schema: bool):
        """Collect a whole apply_edges call, or explain why not.

        Returns None when every edge was collected (conflict keys
        added, columns appended); otherwise a fallback reason string
        and NO state was modified — the caller materializes and runs
        the serial path. Single staged pass: columns build in local
        typed buffers and land with bulk extends on success (this is
        per-edge GIL work on the commit fast path — every attribute
        lookup here is paid tens of thousands of times per second)."""
        if txn.cache.deltas:
            # sticky serial: Python deltas exist (a prior materialize
            # or slow-path call) — mixing would double-write keys at
            # one commit_ts (MemKV same-ts puts overwrite)
            return "mixed_txn"
        st_get = st.get
        preds_get = self._preds.get
        scalar_seen = self._scalar_seen
        data_key = keys.DataKey
        default_tid = TypeID.DEFAULT
        sh = bytearray()
        en = array("Q")
        pi = array("i")
        ob = array("Q")
        vt = bytearray()
        vb = bytearray()
        vo = array("q")
        vbase = len(self.vblob)
        cks: List[tuple] = []  # staged add_conflict_key arg tuples
        seen_add: List[tuple] = []  # staged _scalar_seen additions
        probe = []  # data keys pending the live-prior-values read
        call_scalar: set = set()
        nposts = 0
        for e in edges:
            if e.op != OP_SET:
                return "delete"
            if e.facets:
                return "facets"
            if e.lang:
                return "lang"
            attr = e.attr
            ns = e.ns
            su = st_get(attr)
            if su is None:
                if not update_schema:
                    return "schema"  # serial path raises the error
                tid = (
                    TypeID.UID
                    if e.value_id is not None
                    else (e.value.tid if e.value else default_tid)
                )
                su = st.ensure_default(attr, tid)
            pred = preds_get((ns, attr))
            if pred is None or pred.su is not su:
                pred = self._pred_for(su, attr, ns)
            entity = e.entity
            if e.value_id is not None:
                if not pred.uid_ok:
                    return pred.uid_reason
                obj = int(e.value_id)
                sh.append(1)
                en.append(entity)
                pi.append(pred.pid)
                ob.append(obj)
                vt.append(0)
                vo.append(vbase + len(vb))
                dk = data_key(attr, entity, ns)
                cks.append((
                    dk if pred.upsert else dk + b"#u",
                    str(obj).encode(),
                ))
                if pred.flags & PF_REVERSE:
                    cks.append((
                        keys.ReverseKey(attr, obj, ns),
                        str(entity).encode(),
                    ))
                nposts += pred.est_uid
                continue
            value = e.value
            if value is None:
                return "shape"  # serial path raises the error
            if not pred.scalar_ok:
                return pred.scalar_reason
            vt_id = su.value_type
            try:
                stored = (
                    convert(value, vt_id)
                    if vt_id != default_tid
                    else value
                )
                vbytes = to_binary(stored)
            except Exception:
                return "convert"  # serial path raises the error
            flags = pred.flags
            if flags & PF_TERM and not str(stored.value).isascii():
                return "ascii"  # unicode terms: Python tokenizer
            skey = (ns, attr, entity)
            if skey in call_scalar:
                # serial demotes shared-key edges to the per-edge loop
                return "shared_key"
            call_scalar.add(skey)
            dk = data_key(attr, entity, ns)
            if flags & _PF_TOKS:
                if skey in scalar_seen:
                    # overwriting an earlier columnar write needs the
                    # deindex-old-tokens path
                    return "deindex"
                if not e.fresh:
                    probe.append(dk)
            sh.append(0)
            en.append(entity)
            pi.append(pred.pid)
            ob.append(0)
            vt.append(int(stored.tid))
            vb += vbytes
            vo.append(vbase + len(vb))
            cks.append((dk if pred.upsert else dk + b"#v",))
            seen_add.append(skey)
            nposts += pred.est_scalar
        if probe:
            # the deindex check: keys holding live prior values must
            # delete old index tokens first (serial-path territory)
            oldvals = txn.cache.values_many(probe)
            if any(oldvals):
                return "deindex"
        # every edge is eligible — commit the call atomically
        add_ck = txn.add_conflict_key
        for args in cks:
            add_ck(*args)
        self.shapes += sh
        self.entities += en
        self.pids += pi
        self.objects += ob
        self.vtypes += vt
        self.vblob += vb
        self.voffs += vo
        scalar_seen.update(seen_add)
        self.nposts_est += nposts
        self._chunks.append((st, list(edges), update_schema))
        return None

    def take_chunks(self) -> List[tuple]:
        """Drain for materialize: returns the collected (st, edges,
        update_schema) calls and resets every column."""
        chunks = self._chunks
        self._chunks = []
        self.shapes = bytearray()
        self.entities = array("Q")
        self.pids = array("i")
        self.objects = array("Q")
        self.vtypes = bytearray()
        self.voffs = array("q", (0,))
        self.vblob = bytearray()
        self._scalar_seen = set()
        self.nposts_est = 0
        # pred plans stay cached: pids are only meaningful to columns
        return chunks

    def fence_keys(self) -> List[bytes]:
        """One representative data key per collected predicate — what
        the tablet-move fence check parses attrs from (the columns
        carry no concrete keys until the kernel runs)."""
        return [
            keys.DataKey(p.attr, 0, p.ns)
            for p in self.pred_list
        ]

    def note_traffic(self) -> None:
        """Per-tablet mutation accounting at encode time (the serial
        path counts per edge at apply time)."""
        if not observe.tablet_traffic_enabled():
            return
        counts: Dict[int, int] = {}
        for pid in self.pids:
            counts[pid] = counts.get(pid, 0) + 1
        for pid, n in counts.items():
            p = self.pred_list[pid]
            observe.TABLETS.note_write(p.ns, p.attr, n)


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------


def columnar_ok(engine) -> bool:
    """May this engine's commits consume columnar write sets right now?
    Checked at txn creation AND again at commit (a CDC sink or vector
    index registered mid-txn forces a materialize): every commit-time
    consumer of Posting objects must be absent."""
    from dgraph_tpu import native

    if not native.NATIVE_AVAILABLE or not bool(config.get("BATCH_APPLY")):
        return False
    if getattr(engine, "_cdc", None) is not None:
        return False
    if getattr(engine, "_subscriptions", None) is not None:
        return False
    if getattr(engine, "vector_indexes", None):
        return False
    return True


def maybe_enable(txn, engine) -> None:
    """Attach a columnar write set to a fresh engine txn when the
    batch-apply path is available."""
    if columnar_ok(engine):
        txn.col = ColumnarWriteSet()


def commit_guard(txn, engine) -> None:
    """Commit-entry check: if a consumer that needs Posting objects
    appeared after the txn was created (CDC, subscriptions, vector
    index), fall back to the serial representation now."""
    col = getattr(txn, "col", None)
    if col is not None and col.pending and not columnar_ok(engine):
        count_fallback("engine", len(col.shapes))
        materialize(txn)


def materialize(txn) -> None:
    """Replay collected calls through the serial apply path into
    txn.cache.deltas (byte-identical outcome), disabling further
    collection for this txn (sticky: deltas are now non-empty)."""
    col = txn.col
    if col is None:
        return
    txn.col = None  # replay must not re-collect
    if not col.pending:
        return
    from dgraph_tpu.posting.mutation import _apply_edges_fallback

    chunks = col.take_chunks()
    for st, edges, update_schema in chunks:
        _apply_edges_fallback(txn, st, edges, update_schema)


# ---------------------------------------------------------------------------
# Commit-time encode (the kernel call)
# ---------------------------------------------------------------------------


def _pred_blobs(pred_tab: List[_Pred]):
    """(pp_blob, pp_offs, pflags, pidents) for a pred table."""
    pp_offs = array("q", (0,))
    parts = []
    pos = 0
    for p in pred_tab:
        parts.append(p.prefix)
        pos += len(p.prefix)
        pp_offs.append(pos)
    return (
        b"".join(parts),
        pp_offs,
        bytes(p.flags for p in pred_tab),
        b"".join(p.idents for p in pred_tab),
    )


def flatten_colsets(colsets: List[ColumnarWriteSet]):
    """The merged batch arrays the kernel (and the apply-shard
    processes' wire payload) consume: ((m_offs, shapes, entities,
    pids, objects, vtypes, voffs, vblob), pred_tab) with the members'
    pred ids remapped onto one deduplicated pred table. Single-colset
    calls (serial commits, 1-member batches) pass the collected
    buffers straight through — zero concatenation."""
    if len(colsets) == 1:
        cs = colsets[0]
        return (
            (
                array("q", (0, len(cs.shapes))), cs.shapes,
                cs.entities, cs.pids, cs.objects, cs.vtypes,
                cs.voffs, cs.vblob,
            ),
            cs.pred_list,
        )
    merged: Dict[tuple, int] = {}
    pred_tab = []
    remaps: List[List[int]] = []
    for cs in colsets:
        remap = []
        for p in cs.pred_list:
            mk = (p.ns, p.attr, p.flags, p.idents, p.prefix)
            b = merged.get(mk)
            if b is None:
                b = merged[mk] = len(pred_tab)
                pred_tab.append(p)
            remap.append(b)
        remaps.append(remap)
    m_offs = array("q", (0,))
    shapes = bytearray()
    entities = array("Q")
    pids = array("i")
    objects = array("Q")
    vtypes = bytearray()
    voffs = array("q", (0,))
    vblob = bytearray()
    for cs, remap in zip(colsets, remaps):
        shapes += cs.shapes
        entities += cs.entities
        if remap == list(range(len(remap))):
            pids += cs.pids  # members usually share one pred order
        else:
            pids.extend(remap[p] for p in cs.pids)
        objects += cs.objects
        vtypes += cs.vtypes
        base = len(vblob)
        vblob += cs.vblob
        if base:
            voffs.extend(v + base for v in cs.voffs[1:])
        else:
            voffs += cs.voffs[1:]
        m_offs.append(len(shapes))
    return (
        (m_offs, shapes, entities, pids, objects, vtypes, voffs, vblob),
        pred_tab,
    )


def _run_kernel(colsets: List[ColumnarWriteSet]):
    """Flatten the colsets (members of one group-commit batch) into the
    batch arrays and run ONE codec.cpp batch_apply call. Returns the
    wrapper's raw result plus the merged pred table, or None when the
    native library refuses."""
    from dgraph_tpu import native

    flat, pred_tab = flatten_colsets(colsets)
    pp_blob, pp_offs, pflags, pidents = _pred_blobs(pred_tab)
    res = native.batch_apply(
        *flat, pp_blob, pp_offs, pflags, pidents,
    )
    if res is None:
        return None
    return res, pred_tab


def _encode_colsets(colsets: List[ColumnarWriteSet]):
    """Per-colset [(key, record, attr)] lists plus per-colset
    (keys, stats_rows, n_postings) side info, or None when the kernel
    is unavailable (caller materializes). With DGRAPH_TPU_APPLY_PROCS
    workers live, the kernel runs in the apply-shard processes
    (worker/applyshard.py) — same result shape, byte-identical pairs;
    any escape from that plane falls through to the in-process call
    below (exact serial semantics, counted per reason)."""
    from dgraph_tpu.worker import applyshard

    pool = applyshard.maybe_pool()
    if pool is not None:
        got = pool.encode(colsets)
        if got is not None:
            METRICS.inc("mutation_batch_apply_total")
            METRICS.inc(
                "mutation_batch_apply_edges_total",
                sum(len(cs.shapes) for cs in colsets),
            )
            return got
    got = _run_kernel(colsets)
    if got is None:
        return None
    (
        n_pairs, keys_blob, key_offs, recs_blob, rec_offs,
        member, pred, kinds, counts,
    ), pred_tab = got
    kidx = keys.KIND_INDEX
    attrs = [p.attr for p in pred_tab]
    plens = [len(p.prefix) + 1 for p in pred_tab]
    out = []
    side = []
    pos = 0
    for mi in range(len(colsets)):
        end = pos
        while end < n_pairs and member[end] == mi:
            end += 1
        pairs = []
        pappend = pairs.append
        mkeys = []
        kappend = mkeys.append
        stats_rows = []
        for i in range(pos, end):
            key = keys_blob[key_offs[i]:key_offs[i + 1]]
            pid = pred[i]
            pappend((key, recs_blob[rec_offs[i]:rec_offs[i + 1]],
                     attrs[pid]))
            kappend(key)
            if kinds[i] == kidx:
                stats_rows.append(
                    (attrs[pid], key[plens[pid]:], counts[i])
                )
        out.append(pairs)
        side.append((mkeys, stats_rows, sum(counts[pos:end])))
        pos = end
    METRICS.inc("mutation_batch_apply_total")
    METRICS.inc(
        "mutation_batch_apply_edges_total",
        sum(len(cs.shapes) for cs in colsets),
    )
    return out, side


def encode_txn(txn) -> List[Tuple[bytes, bytes, str]]:
    """Serial-commit encode of one txn's columnar write set: returns
    ready-to-put (key, record, attr) triples and stamps the side
    channels (col_keys for invalidation, col_records for the value
    columns, col_stats for the selectivity sketch, col_nposts for the
    postings-written metric). Falls back to
    materialize (returning []) when the kernel refuses — the caller's
    ordinary deltas path then handles everything."""
    col = getattr(txn, "col", None)
    if col is None or not col.pending:
        return []
    got = _encode_colsets([col])
    if got is None:
        count_fallback("kernel", len(col.shapes))
        materialize(txn)
        return []
    out, side = got
    mkeys, stats_rows, nposts = side[0]
    txn.col_keys = mkeys
    txn.col_records = out[0]
    txn.col_stats = stats_rows
    txn.col_nposts = nposts
    col.note_traffic()
    col.take_chunks()  # consumed
    return out[0]


def batch_encode(members) -> Dict[object, List[Tuple[bytes, bytes, str]]]:
    """Group-commit leader encode: ALL committed members' columnar
    write sets through ONE kernel call. Returns {member: [(key,
    record, attr)]} for members that had columns (stamping the same
    per-txn side channels as encode_txn); members whose colsets had to
    materialize simply keep their Python deltas and are absent."""
    live = [
        m
        for m in members
        if getattr(m.txn, "col", None) is not None and m.txn.col.pending
    ]
    if not live:
        return {}
    got = _encode_colsets([m.txn.col for m in live])
    if got is None:
        for m in live:
            count_fallback("kernel", len(m.txn.col.shapes))
            materialize(m.txn)
        return {}
    out, side = got
    result = {}
    for m, pairs, (mkeys, stats_rows, nposts) in zip(live, out, side):
        m.txn.col_keys = mkeys
        m.txn.col_records = pairs
        m.txn.col_stats = stats_rows
        m.txn.col_nposts = nposts
        m.txn.col.note_traffic()
        m.txn.col.take_chunks()  # consumed
        result[m] = pairs
    return result


def fence_keys(txn) -> List[bytes]:
    """Keys the tablet-move fence check should parse for this txn:
    Python delta keys plus one synthetic data key per columnar
    predicate."""
    ks = list(txn.cache.deltas)
    col = getattr(txn, "col", None)
    if col is not None and col.pending:
        ks.extend(col.fence_keys())
    return ks


def feed_col_stats(stats, txn) -> None:
    """Index-posting counts from the kernel's output into the
    selectivity sketch — what cmsketch.feed_stats does for Python
    deltas."""
    rows = getattr(txn, "col_stats", None)
    if rows:
        for attr, term, n in rows:
            stats.record(attr, term, n)
