"""In-process API front-end: the edgraph.Server equivalent.

Mirrors /root/reference/edgraph/server.go: Query (doQuery:1396),
Mutate (doMutate:575), Alter (:355 schema & drop ops),
CommitOrAbort (:2108) — single-process round 1 with the ZeroLite seam
standing in for the Zero cluster (ref hooks/config.go ZeroHooks).

Mutations accept RDF text (set/delete) or structured edges; blank nodes
(`_:x`) get fresh uids (ref query/mutation.go:187 AssignUids). Queries run
through dql.parse -> query.Executor -> JsonEncoder.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from dgraph_tpu import dql
from dgraph_tpu.loaders.rdf import NQuad, parse_rdf
from dgraph_tpu.posting import colwrite
from dgraph_tpu.posting.lists import LocalCache, Txn
from dgraph_tpu.posting.mutation import (
    DirectedEdge,
    apply_edge,
    apply_edges,
    delete_entity_attr,
    ingest_vectors,
)
from dgraph_tpu.posting.pl import OP_DEL, OP_SET, encode_deltas
from dgraph_tpu.worker.groupcommit import (
    assign_verdicts,
    columnar_writes,
    commit_phase_ns,
)
from dgraph_tpu.query.streamjson import encode_response_data
from dgraph_tpu.query.subgraph import Executor
from dgraph_tpu.serving.digest import DIGESTS
from dgraph_tpu.schema.schema import State, parse_schema
from dgraph_tpu.storage.kv import KV, open_kv
from dgraph_tpu.types.types import TypeID, Val
from dgraph_tpu.utils import observe
from dgraph_tpu.x import keys
from dgraph_tpu.zero.zero import TxnConflictError, ZeroLite


@contextlib.contextmanager
def _applying(txn: Txn, edges: Optional[int]):
    """`mutate.apply` around one mutation's edges going into `txn`:
    `edges` (the N-Quads applied, where the caller counted them) and
    `keys` (keys holding a delta of the transaction afterwards; a
    columnar write set names its keys only at commit)."""
    with observe.TRACER.span("mutate.apply", cpu=True, fine=True) as sp:
        yield
        if edges is not None:
            sp.attrs["edges"] = edges
        sp.attrs["keys"] = len(txn.cache.deltas)


class TxnHandle:
    """Client-side transaction handle (dgo Txn equivalent)."""

    def __init__(self, server: "Server", read_only: bool = False):
        self.server = server
        self.start_ts = server.zero.begin_txn()
        self.txn = Txn(server.kv, self.start_ts, mem=server.mem)
        self.read_only = read_only
        self.finished = False
        if not read_only:
            colwrite.maybe_enable(self.txn, server)

    def query(self, q: str, access_jwt: Optional[str] = None) -> dict:
        """Query within this txn's snapshot (sees own uncommitted writes)."""
        self.txn.materialize_cols()  # read-your-writes over columns
        blocks = dql.parse(q)
        ns = keys.GALAXY_NS
        allowed = None
        if self.server.acl is not None:
            from dgraph_tpu.acl.acl import READ, AclError

            if access_jwt is None:
                raise AclError("no access token (ACL enabled)")
            claims = self.server.acl.claims(access_jwt)
            ns = int(claims.get("namespace", 0))
            self.server.acl.authorize_preds(
                access_jwt, _query_preds(blocks), READ, claims=claims
            )
            allowed = self.server.acl.readable_preds(claims)
        return self.server._query_parsed(blocks, self.txn.cache, ns, allowed)

    def mutate_rdf(
        self,
        set_rdf: str = "",
        del_rdf: str = "",
        commit_now: bool = False,
        access_jwt: Optional[str] = None,
    ) -> Dict[str, str]:
        from dgraph_tpu.loaders.rdf import parse_rdf as _prdf

        with observe.TRACER.span("mutate.parse", cpu=True, fine=True) as sp:
            set_nqs, del_nqs = _prdf(set_rdf), _prdf(del_rdf)
            sp.attrs["nquads"] = len(set_nqs) + len(del_nqs)
        observe.METRICS.inc("mutate_nquads_total", len(set_nqs) + len(del_nqs))
        body = f"set:{set_rdf!r} del:{del_rdf!r}"
        ns, user = self.server._authorize_mutation(
            access_jwt,
            sorted({nq.predicate for nq in set_nqs + del_nqs}),
            body,
        )
        self.txn.tenant_ns = ns  # per-tenant commit SLO slice
        with _applying(self.txn, len(set_nqs) + len(del_nqs)):
            uids = self.server._apply_nquads(self.txn, set_nqs, del_nqs, ns)
        if commit_now:
            self.commit()
        return uids

    def mutate_json(
        self,
        set_obj=None,
        del_obj=None,
        commit_now: bool = False,
        access_jwt: Optional[str] = None,
    ):
        if self.server.acl is None and self.server.audit is None:
            # the common unsecured path: computing the predicate set
            # and dumping the audit body would be pure waste per write
            ns = keys.GALAXY_NS
        else:
            body = json.dumps(
                {"set": set_obj, "delete": del_obj}, default=str
            )
            ns, _ = self.server._authorize_mutation(
                access_jwt,
                sorted(_json_preds(set_obj) | _json_preds(del_obj)),
                body,
            )
        self.txn.tenant_ns = ns  # per-tenant commit SLO slice
        with _applying(self.txn, None):
            uids = self.server._apply_json(self.txn, set_obj, del_obj, ns)
        if commit_now:
            self.commit()
        return uids

    def _upsert_prologue(
        self, query: str, mutation_preds_fn, access_jwt: Optional[str]
    ):
        """Shared upsert front half: ACL (READ on query preds, WRITE on
        mutation preds, JWT namespace) + query execution binding
        uid/val vars. `mutation_preds_fn` is called only when ACL is on
        (computing preds means parsing the mutation — skip it for the
        common unsecured path). Returns (ns, uid_vars, val_vars)."""
        blocks = dql.parse(query) if query.strip() else []
        ns = keys.GALAXY_NS
        if self.server.acl is not None:
            from dgraph_tpu.acl.acl import READ, WRITE, AclError

            if access_jwt is None:
                raise AclError("no access token (ACL enabled)")
            claims = self.server.acl.claims(access_jwt)
            ns = int(claims.get("namespace", 0))
            self.server.acl.authorize_preds(
                access_jwt, _query_preds(blocks), READ, claims=claims
            )
            self.server.acl.authorize_preds(
                access_jwt, sorted(mutation_preds_fn()), WRITE,
                claims=claims,
            )
        uid_vars: Dict[str, List[int]] = {}
        val_vars: Dict[str, dict] = {}
        if blocks:
            self.txn.materialize_cols()  # upsert query reads own writes
            ex = Executor(
                self.txn.cache,
                self.server.schema,
                ns=ns,
                vector_indexes=self.server.vector_indexes,
            )
            ex.process(blocks)
            uid_vars = {
                k: [int(u) for u in v] for k, v in ex.uid_vars.items()
            }
            val_vars = ex.val_vars
        return ns, uid_vars, val_vars

    def upsert(
        self,
        query: str,
        set_rdf: str = "",
        del_rdf: str = "",
        cond: Optional[str] = None,
        commit_now: bool = True,
        access_jwt: Optional[str] = None,
    ) -> Dict[str, str]:
        """Upsert block: run query, substitute uid(v)/val(v) refs in the
        mutation, apply (ref edgraph/server.go:874 buildUpsertQuery +
        dql upsert blocks). `cond` is '@if(eq(len(v), 0))'-style gate."""
        def mpreds():
            from dgraph_tpu.loaders.rdf import parse_rdf as _prdf

            return {
                nq.predicate for nq in _prdf(set_rdf) + _prdf(del_rdf)
            }

        ns, uid_vars, val_vars = self._upsert_prologue(
            query, mpreds, access_jwt
        )
        if cond is not None and not _eval_cond(cond, uid_vars):
            if commit_now:
                self.commit()
            return {}

        out = self.server._apply_rdf_with_vars(
            self.txn, set_rdf, del_rdf, uid_vars, val_vars, ns=ns
        )
        if commit_now:
            self.commit()
        return out

    def upsert_json(
        self,
        query: str,
        mutations: List[dict],
        commit_now: bool = True,
        access_jwt: Optional[str] = None,
    ) -> Dict[str, str]:
        """Multi-mutation JSON upsert: one query block binding uid vars,
        then a list of {"set": obj, "delete": obj, "cond": "@if(...)"}
        mutations applied against those bindings (ref edgraph/server.go
        doQuery with req.Mutations[] — the shape the GraphQL rewriters
        emit, graphql/resolve/mutation_rewriter.go UpsertMutation)."""
        def mpreds():
            return {
                p
                for m in mutations
                for p in (
                    _json_preds(m.get("set"))
                    | _json_preds(m.get("delete"))
                )
            }

        ns, uid_vars, val_vars = self._upsert_prologue(
            query, mpreds, access_jwt
        )
        blanks: Dict[str, int] = {}  # blank-node map SHARED across the
        # request's mutations (ref: one AssignUids per request)
        for m in mutations:
            cond = m.get("cond")
            if cond and not _eval_cond(cond, uid_vars):
                continue
            self.server._apply_json_with_vars(
                self.txn, m.get("set"), m.get("delete"), uid_vars,
                ns=ns, blank=blanks, val_vars=val_vars,
            )
        if commit_now:
            self.commit()
        return {k[2:]: hex(v) for k, v in blanks.items()}

    def commit(self) -> int:
        if self.finished:
            raise RuntimeError("transaction already finished")
        self.finished = True
        return self.server._commit(self.txn)

    def discard(self):
        self.finished = True
        self.server.zero.abort(self.start_ts)


class Server:
    """Single-node engine (Alpha + embedded Zero-lite)."""

    def __init__(
        self,
        data_dir: Optional[str] = None,
        encryption_key: Optional[bytes] = None,
    ):
        self.kv: KV = open_kv(data_dir, encryption_key=encryption_key)
        self.zero = ZeroLite()
        self.schema = State()
        self.vector_indexes: Dict[str, object] = {}
        self._lock = threading.Lock()
        self._group_commit = None  # lazy (worker/groupcommit.py)
        from dgraph_tpu.posting.memlayer import MemoryLayer

        self.acl = None  # enabled via enable_acl() (ref --acl superflag)
        self.audit = None  # enabled via enable_audit()
        from dgraph_tpu.x import config as _config

        # slow-query threshold (instance override of the registry knob)
        self.slow_query_ms = float(_config.get("SLOW_QUERY_MS"))
        self.mem = MemoryLayer()  # shared decoded-list read cache
        # resident value columns (query/valcol.py): this engine tells
        # them of a commit before its timestamp is readable
        # (_columns_commit, ahead of every watermark move)
        from dgraph_tpu.query.valcol import ValueColumns

        self.mem.value_columns = ValueColumns()
        from dgraph_tpu.utils.cmsketch import StatsHolder

        self.stats = StatsHolder()  # selectivity stats (auto-fed on commit)
        from dgraph_tpu.serving import ServingFront

        # high-QPS serving front: plan cache + cross-query micro-batcher
        # + admission control (serving/). _snapshot_ts is the batcher's
        # snapshot watermark: the last commit made VISIBLE (published
        # before zero.applied, the barrier read_ts waits on), so two
        # fresh read timestamps covering the same watermark coalesce.
        self._snapshot_ts = 0
        self.serving = ServingFront(
            stats=self.stats,
            schema_fn=lambda: self.schema,
            last_commit_fn=lambda: self._snapshot_ts,
        )
        self._bootstrap_schema()
        if data_dir is not None:
            self._load_persisted_state()
        # warm the native C++ layer off the request path (first import
        # compiles codec.cpp; without this the first query/rollup pays it)
        threading.Thread(
            target=lambda: __import__("dgraph_tpu.native"), daemon=True
        ).start()

    # -- security (ref edgraph/access.go; audit/) -----------------------------

    def enable_acl(self, secret: Optional[bytes] = None, groot_password="password"):
        from dgraph_tpu.acl.acl import AclManager

        self.acl = AclManager(self, secret)
        self.acl.bootstrap(groot_password=groot_password)
        return self.acl

    def enable_audit(self, out_dir: str, key: Optional[bytes] = None):
        from dgraph_tpu.audit.audit import AuditLog

        self.audit = AuditLog(out_dir, key=key)
        return self.audit

    def login(self, user: str, password: str, ns: int = keys.GALAXY_NS):
        if self.acl is None:
            raise RuntimeError("ACL not enabled")
        try:
            out = self.acl.login(user, password, ns)
            self._audit("login", user=user, ns=ns)
            return out
        except Exception:
            self._audit("login", user=user, ns=ns, status="DENIED")
            raise

    def _audit(self, endpoint, user="", ns=0, body="", status="OK"):
        if self.audit is not None:
            self.audit.record(endpoint, user=user, ns=ns, body=body, status=status)

    def _authorize(self, access_jwt, preds, need) -> int:
        """Returns the caller's namespace (0 when ACL off)."""
        if self.acl is None:
            return keys.GALAXY_NS
        from dgraph_tpu.acl.acl import AclError

        if access_jwt is None:
            raise AclError("no access token (ACL enabled)")
        claims = self.acl.claims(access_jwt)
        self.acl.authorize_preds(access_jwt, preds, need)
        return int(claims.get("namespace", 0))

    def _authorize_mutation(self, access_jwt, preds, audit_body):
        """WRITE authorization + audit for any mutation entry point.
        Returns (namespace, user)."""
        ns, user = keys.GALAXY_NS, ""
        if self.acl is not None:
            from dgraph_tpu.acl.acl import WRITE, AclError

            try:
                if access_jwt is None:
                    raise AclError("no access token (ACL enabled)")
                claims = self.acl.claims(access_jwt)
                user = claims.get("userid", "")
                ns = int(claims.get("namespace", 0))
                self.acl.authorize_preds(
                    access_jwt, preds, WRITE, claims=claims
                )
            except Exception:
                self._audit(
                    "mutate", user=user, body=audit_body, status="DENIED"
                )
                raise
        self._audit("mutate", user=user, ns=ns, body=audit_body)
        return ns, user

    def _apply_nquads(self, txn, set_nqs, del_nqs, ns) -> Dict[str, str]:
        blank: Dict[str, int] = {}
        fresh_uids: set = set()  # uids leased by THIS request

        def resolve(ref: str) -> int:
            if ref.startswith("_:"):
                if ref not in blank:
                    blank[ref] = self.zero.assign_uids(1)
                    fresh_uids.add(blank[ref])
                return blank[ref]
            if ref.startswith("0x"):
                return int(ref, 16)
            return int(ref)

        # batched application: plain edges accumulate and flush through
        # apply_edges (bulk reads + bulk tokens); a star delete flushes
        # first so it observes every edge that preceded it in order
        pending: List[DirectedEdge] = []

        def flush():
            if pending:
                apply_edges(txn, self.schema, pending)
                pending.clear()

        for nqs, op in ((set_nqs, OP_SET), (del_nqs, OP_DEL)):
            for nq in nqs:
                if nq.star:
                    if op != OP_DEL:
                        raise ValueError("S P * only valid in delete")
                    flush()
                    delete_entity_attr(
                        txn, self.schema, resolve(nq.subject),
                        nq.predicate, ns,
                    )
                    continue
                e = self._nquad_edge(nq, resolve, op, ns=ns)
                e.fresh = e.entity in fresh_uids
                pending.append(e)
        flush()
        return {k[2:]: hex(v) for k, v in blank.items()}

    def _bootstrap_schema(self):
        # system predicates (ref schema/schema.go initialSchema)
        for su in parse_schema(
            "dgraph.type: [string] @index(exact) .\n"
            "dgraph.xid: string @index(exact) .\n"
        )[0]:
            self.schema.set(su)

    def _load_persisted_state(self):
        """Recover schema + max ts/uid from the KV after restart (ref
        schema load in schema/schema.go LoadFromDb; Zero state from raft)."""
        max_ts = 0
        max_uid = 0
        for key, vers in self.kv.iterate_versions(b"", (1 << 62)):
            if vers:
                max_ts = max(max_ts, vers[0][0])
            try:
                pk = keys.parse_key(key)
            except Exception:
                continue  # non-graph meta keys (e.g. namespace counter)
            if pk.uid is not None:
                max_uid = max(max_uid, pk.uid)
            if pk.is_schema:
                preds, _ = parse_schema(vers[0][1].decode("utf-8"))
                for su in preds:
                    self.schema.set(su)
                    if su.vector_specs:
                        self._ensure_vector_index(su)
            elif pk.is_type:
                _, types = parse_schema(vers[0][1].decode("utf-8"))
                for tu in types:
                    self.schema.set_type(tu)
        while self.zero.max_assigned < max_ts:
            self.zero.next_ts(max_ts - self.zero.max_assigned)
        # re-lease uids past everything on disk, or fresh blank nodes would
        # reuse (and overwrite) existing entities' uids
        if max_uid and max_uid < (1 << 62) and self.zero._max_uid <= max_uid:
            self.zero.assign_uids(max_uid - self.zero._max_uid)
        # seed the snapshot watermark past everything recovered, so
        # watermark reads see the restored store from the first query
        # (max()-guarded: online restore can run beside live commits)
        self._snapshot_ts = max(self._snapshot_ts, self.zero.read_ts())
        self._columns_reset()
        self.rebuild_vector_indexes()

    def rebuild_vector_indexes(self):
        """Re-ingest stored vectors into the in-memory vector indexes
        (ref posting/index.go:1354 vector-index rebuild prefixes)."""
        ts = self.zero.read_ts()
        read = LocalCache(self.kv, ts)
        for pred in self.schema.predicates():
            su = self.schema.get(pred)
            if not su or not su.vector_specs:
                continue
            self._ensure_vector_index(su)
            vidx = self.vector_indexes[pred]
            for k, _, _ in self.kv.iterate(keys.DataPrefix(pred), ts):
                pk = keys.parse_key(k)
                for p in read.values(k):
                    vidx.insert(pk.uid, p.val().value)

    # -- alter (ref edgraph/server.go:355) -----------------------------------

    def alter(self, schema_text: str = "", drop_attr: str = "", drop_all: bool = False):
        self.serving.on_commit()  # schema changes invalidate cached plans
        try:
            return self._alter_inner(schema_text, drop_attr, drop_all)
        finally:
            # alters write outside the txn/applied barrier: advance the
            # batcher watermark past every read_ts allocated during the
            # alter, so queries that raced the (non-transactional)
            # schema writes never coalesce with post-alter traffic
            # (max()-guarded like every other watermark writer)
            self._snapshot_ts = max(
                self._snapshot_ts, self.zero.next_ts()
            )
            self._columns_reset()

    def _alter_inner(self, schema_text, drop_attr, drop_all):
        with self._lock:
            if drop_all:
                # wipe every key (data + persisted schema/types) so a
                # restart cannot resurrect dropped state
                self.kv.drop_prefix(b"")
                self.schema = State()
                self._bootstrap_schema()
                self.vector_indexes.clear()
                return
            if drop_attr:
                self.kv.drop_prefix(keys.PredicatePrefix(drop_attr))
                self.kv.drop_prefix(keys.SplitPredicatePrefix(drop_attr))
                self.kv.drop_prefix(keys.SchemaKey(drop_attr))
                self.schema.delete(drop_attr)
                self.vector_indexes.pop(drop_attr, None)
                return
            preds, types = parse_schema(schema_text)
            ts = self.zero.next_ts()
            from dgraph_tpu.admin.export import _schema_line

            for su in preds:
                old = self.schema.get(su.predicate)
                self.schema.set(su)
                self.kv.put(
                    keys.SchemaKey(su.predicate),
                    ts,
                    _schema_line(su).encode("utf-8"),
                )
                if su.vector_specs:
                    self._ensure_vector_index(su)
                if old is not None and (
                    old.tokenizers != su.tokenizers
                ):
                    self._reindex(su)
            for tu in types:
                self.schema.set_type(tu)
                fields = "\n  ".join(tu.fields)
                self.kv.put(
                    keys.TypeKey(tu.name),
                    ts,
                    f"type {tu.name} {{\n  {fields}\n}}\n".encode("utf-8"),
                )

    def bump_snapshot(self) -> int:
        """Advance the snapshot watermark past every timestamp leased
        so far. Direct-KV writers that bypass the commit path (bulk
        loaders, the namespace counter) MUST call this after their
        writes land, or watermark reads would never see them; commits
        and alters advance it themselves. Returns the new watermark.
        max()-guarded like every other watermark writer: a commit
        leased after our read_ts may publish a larger watermark before
        this assignment runs."""
        self._snapshot_ts = max(self._snapshot_ts, self.zero.read_ts())
        self._columns_reset()
        return self._snapshot_ts

    def _ensure_vector_index(self, su):
        from dgraph_tpu.models.vector import VectorIndex

        if su.predicate not in self.vector_indexes:
            self.vector_indexes[su.predicate] = VectorIndex(
                pred=su.predicate,
                metric=su.vector_specs[0].metric,
            )

    def _reindex(self, su):
        """Full index rebuild for a predicate (ref posting/index.go:1115
        IndexRebuild): drop index range, re-tokenize all values."""
        pred = su.predicate
        self.kv.drop_prefix(keys.IndexPrefix(pred))
        ts = self.zero.next_ts()
        read = LocalCache(self.kv, ts)
        from dgraph_tpu.posting.pl import Posting
        from dgraph_tpu.tok.tok import build_tokens

        tokenizers = su.tokenizer_objs()
        if not tokenizers:
            return
        from dgraph_tpu.posting.pl import encode_delta

        # aggregate uids per index key: entities sharing a token must land
        # in ONE record, since MemKV overwrites same-(key, ts) versions
        # (ref posting/index.go IndexRebuild emits complete per-key lists)
        by_key: Dict[bytes, set] = {}
        for k, _, _ in self.kv.iterate(keys.DataPrefix(pred), ts):
            pk = keys.parse_key(k)
            for p in read.values(k):
                for tokb in build_tokens(p.val(), tokenizers):
                    by_key.setdefault(keys.IndexKey(pred, tokb), set()).add(pk.uid)
        self.kv.put_batch(
            (
                ikey,
                ts,
                encode_delta([Posting(uid=u, op=OP_SET) for u in sorted(uids)]),
            )
            for ikey, uids in by_key.items()
        )

    # -- transactions ---------------------------------------------------------

    def new_txn(self, read_only: bool = False) -> TxnHandle:
        return TxnHandle(self, read_only)

    def _commit(self, txn: Txn) -> int:
        from dgraph_tpu.x import config as _config

        from dgraph_tpu.utils.observe import METRICS as _METRICS

        # a commit-time consumer of Posting objects that appeared after
        # txn creation (CDC sink, subscription, vector index) forces
        # collected columns back to the serial representation
        colwrite.commit_guard(txn, self)
        # admission costs writes too: a commit charges the same
        # in-flight token budget queries draw from (retryable 429 over
        # budget; no-op with DGRAPH_TPU_ADMISSION off)
        n_edges = txn.pending_postings()
        ticket = self.serving.admit_write(n_edges)
        t_commit0 = time.monotonic()
        # `commit.wait`: this committer's time from asking to commit to
        # its commit's apply barrier; where its own thread ran the batch
        # (or the serial path), the `commit` span inside holds that work
        waited = observe.TRACER.span("commit.wait", fine=True)
        try:
            if not bool(_config.get("GROUP_COMMIT")):
                # escape hatch (DGRAPH_TPU_GROUP_COMMIT=0): today's
                # serial per-txn path, byte-for-byte
                with waited:
                    commit_ts = self._commit_serial(txn)
            else:
                gc = self._group_commit
                if gc is None:
                    with self._lock:
                        gc = self._group_commit
                        if gc is None:
                            from dgraph_tpu.worker.groupcommit import (
                                GroupCommit,
                            )

                            gc = self._group_commit = GroupCommit(
                                self._gc_propose,
                                serial_fn=self._gc_serial,
                            )
                with _METRICS.timer("commit_latency_seconds"), waited:
                    commit_ts = gc.commit(txn)
                if not getattr(txn, "gc_bypassed", False):
                    # the bypass ran the serial path, whose inline
                    # post-commit work already happened
                    self._post_commit(txn, commit_ts)
            # counted for BOTH arms (only on success — the metric is
            # postings WRITTEN): the A/B escape hatch must not turn
            # the edge-throughput denominator dark. Recounted after
            # the commit: the columnar kernel reports its exact
            # posting count (n_edges above was the admission estimate)
            _METRICS.inc(
                "mutation_edges_total",
                sum(len(p) for p in txn.cache.deltas.values())
                + getattr(txn, "col_nposts", 0),
            )
            # per-tenant SLO slice: mutate paths stamp the resolved
            # namespace onto the txn; untagged txns (direct _commit
            # callers) count against the galaxy default
            observe.note_tenant(
                "commit",
                getattr(txn, "tenant_ns", keys.GALAXY_NS),
                time.monotonic() - t_commit0,
            )
            return commit_ts
        finally:
            self.serving.release_write(ticket)

    def _gc_propose(self, members):
        """Group-commit propose phase (batch leader's thread): ONE
        oracle exchange decides every member, then all committed
        members' deltas land under ONE lock hold. Returns the apply
        barrier (watermark + zero.applied in commit-ts order)."""
        from dgraph_tpu.utils.observe import METRICS, TRACER

        METRICS.inc("commit_batches_total")
        # the batch's work, once, in the tree of the thread that leads
        # it; its apply barrier runs later on the same thread and adds
        # `apply_ms` when done
        with TRACER.span("commit", batch=len(members)) as sp:
            t0 = time.perf_counter_ns()
            committed = assign_verdicts(
                members,
                self.zero.commit_batch(
                    [
                        (m.txn.start_ts, m.txn.conflict_keys)
                        for m in members
                    ],
                    track=True,
                ),
            )
            t1 = time.perf_counter_ns()
            try:
                # encode OUTSIDE the lock — columnar members through
                # ONE batch_apply kernel call (worker/groupcommit
                # columnar_writes, which must precede encode_deltas: a
                # materialized fallback lands in cache.deltas), the
                # rest through posting/pl.encode_deltas (one native
                # batched call per txn) — then all batch members'
                # writes land in ONE put_batch under one lock hold
                col_writes = columnar_writes(committed)
                writes = []
                for m in committed:
                    cts = m.commit_ts
                    for key, recb, _attr in col_writes.get(m, ()):
                        writes.append((key, cts, recb))
                    for key, recb in encode_deltas(m.txn.cache.deltas):
                        writes.append((key, cts, recb))
                with self._lock:
                    self.kv.put_batch(writes)
            except Exception as e:
                # NEVER raise past the oracle: the verdicts are
                # tracked pending, and only the barrier below clears
                # them — an exception escaping here would leak
                # _pending entries and stall every later
                # begin_txn/read_ts for the full wait bound
                for m in committed:
                    if m.error is None:
                        m.error = e
            t2 = time.perf_counter_ns()
            commit_phase_ns(oracle=t1 - t0, propose=t2 - t1)
            sp.attrs.update(
                oracle_ms=(t1 - t0) / 1e6, propose_ms=(t2 - t1) / 1e6
            )

        def barrier():
            tb = time.perf_counter_ns()
            try:
                with self._lock:
                    for m in committed:
                        self._columns_commit(
                            m.txn, m.commit_ts, m.error is None)
                        # watermark BEFORE the apply barrier, advanced
                        # in commit-ts order (members cts-ascending,
                        # barriers FIFO) — the micro-batcher's
                        # snapshot-grouping proof needs monotonicity;
                        # max() so a concurrent bump_snapshot (bulk
                        # load, namespace counter) never regresses
                        self._snapshot_ts = max(
                            self._snapshot_ts, m.commit_ts
                        )
                        self.zero.applied(m.commit_ts)
                # CDC rides the FIFO barrier, not _post_commit: members
                # here are commit-ts ascending and barriers run in
                # ticket order, so the sink stream stays strictly
                # commit-ts ordered even across batches
                cdc = getattr(self, "_cdc", None)
                if cdc is not None:
                    for m in committed:
                        if m.error is None:
                            cdc.emit_commit(
                                m.commit_ts, m.txn.cache.deltas
                            )
            finally:
                ok = 0
                for m in committed:
                    self.mem.invalidate(m.txn.cache.deltas.keys())
                    ck = getattr(m.txn, "col_keys", None)
                    if ck:
                        self.mem.invalidate(ck)
                    if m.error is None:
                        ok += 1
                if ok:
                    METRICS.inc("num_commits", ok)
                    self.serving.on_commit()  # ONE epoch bump per batch
                applied = time.perf_counter_ns() - tb
                commit_phase_ns(apply=applied)
                sp.attrs["apply_ms"] = applied / 1e6

        return barrier

    def _columns_commit(self, txn: Txn, commit_ts: int, wrote: bool) -> None:
        """Tell the value columns which keys `commit_ts` wrote, and how
        to read each one's value as the commit left it, before the
        watermark lets a reader see them (query/valcol.py). Where the
        commit's write landed (`wrote`), a key's value is that of what
        it wrote there, its deltas or its columnar record: every posting
        of a scalar predicate has one uid, so the newest write decides
        the value whatever was there before. A commit whose write failed
        is read back from the store at `commit_ts`, a read that gives
        the interpreter's lock away inside the commit barrier: read back
        for every row, the barrier took 10.6-11.2 s of a 45 s
        `snb.mixed16` window on a v5e's host, 0.54-1.37 s without."""
        from dgraph_tpu.posting.pl import PostingList, decode_record

        deltas = txn.cache.deltas
        records = None

        def value_of(key: bytes) -> Optional[Val]:
            nonlocal records
            posts = deltas.get(key) if wrote else None
            if wrote and posts is None:
                if records is None:
                    records = {k: rec for k, rec, _ in
                               getattr(txn, "col_records", None) or ()}
                rec = records.get(key)
                posts = decode_record(rec)[2] if rec is not None else None
            if posts:
                return PostingList(key).get_value("", posts)
            return self._value_at(key, commit_ts)

        self.mem.value_columns.note_commit(
            itertools.chain(deltas, getattr(txn, "col_keys", None) or ()),
            commit_ts, value_of)

    def _value_at(self, key: bytes, ts: int) -> Optional[Val]:
        """A scalar data key's value at `ts`, past every cache; None
        where it has none."""
        from dgraph_tpu.posting.pl import PostingList

        return PostingList.from_versions(
            key, self.kv.versions(key, ts), kv=self.kv, read_ts=ts
        ).get_value()

    def _columns_reset(self) -> None:
        """The store was written past the commit path (a bulk load, an
        alter, a restore): every column goes, and none is built from a
        view older than the new watermark."""
        self.mem.value_columns.clear(self._snapshot_ts)

    def _post_commit(self, txn: Txn, commit_ts: int) -> None:
        """Per-txn post-commit work on the committer's own thread
        (stats feed, CDC, subscriptions, vector ingest) — everything
        after the apply barrier that doesn't need batch ordering."""
        self._feed_stats(txn.cache.deltas)
        colwrite.feed_col_stats(self.stats, txn)
        # CDC emission moved into the batch barrier (strict commit-ts
        # order across group-commit batches)
        subs = getattr(self, "_subscriptions", None)
        if subs is not None:
            subs.on_commit(txn.cache.deltas)
        # vector index ingestion at commit (shared factory seam)
        ingest_vectors(self.vector_indexes, txn.cache.deltas)

    def _gc_serial(self, txn: Txn) -> int:
        """Adaptive group-commit bypass target (worker/groupcommit.py):
        the serial path minus its own latency timer (gc.commit's
        caller already runs one), with the txn marked so _commit skips
        the batch-path _post_commit — the serial path does that work
        inline."""
        txn.gc_bypassed = True
        return self._commit_serial(txn, timed=False)

    def _commit_serial(self, txn: Txn, timed: bool = True) -> int:
        # serialized: MemKV is single-writer, and readers must not see a
        # commit_ts whose deltas aren't written yet (ADVICE r1 #2)
        from dgraph_tpu.utils.observe import METRICS, TRACER

        from dgraph_tpu.worker.groupcommit import commit_phase_ns

        METRICS.inc("commit_batches_total")  # a batch of one
        with TRACER.span("commit", batch=1) as sp, (
            METRICS.timer("commit_latency_seconds")
            if timed
            else contextlib.nullcontext()
        ), self._lock:
            t0 = time.perf_counter_ns()
            commit_ts = self.zero.commit(txn.start_ts, txn.conflict_keys, track=True)
            t1 = time.perf_counter_ns()
            wrote = False
            try:
                txn.write_deltas(self.kv, commit_ts)
                wrote = True
            finally:
                t2 = time.perf_counter_ns()
                self._columns_commit(txn, commit_ts, wrote)
                # watermark BEFORE the apply barrier: any read_ts
                # allocated after this commit becomes visible observes
                # the advanced watermark (micro-batcher snapshot key);
                # max() guards a concurrent bump_snapshot
                self._snapshot_ts = max(self._snapshot_ts, commit_ts)
                self.zero.applied(commit_ts)
                t3 = time.perf_counter_ns()
                commit_phase_ns(oracle=t1 - t0, propose=t2 - t1, apply=t3 - t2)
                sp.attrs.update(oracle_ms=(t1 - t0) / 1e6,
                                propose_ms=(t2 - t1) / 1e6,
                                apply_ms=(t3 - t2) / 1e6)
        METRICS.inc("num_commits")
        self.mem.invalidate(txn.cache.deltas.keys())
        ck = getattr(txn, "col_keys", None)
        if ck:
            self.mem.invalidate(ck)
        self.serving.on_commit()  # commit-epoch plan invalidation
        self._feed_stats(txn.cache.deltas)
        colwrite.feed_col_stats(self.stats, txn)
        cdc = getattr(self, "_cdc", None)
        if cdc is not None:
            cdc.emit_commit(commit_ts, txn.cache.deltas)
        subs = getattr(self, "_subscriptions", None)
        if subs is not None:
            subs.on_commit(txn.cache.deltas)
        # vector index ingestion at commit (factory seam)
        for key, posts in txn.cache.deltas.items():
            pk = keys.parse_key(key)
            vidx = self.vector_indexes.get(pk.attr)
            if vidx is not None and pk.is_data:
                for p in posts:
                    if p.is_value and p.op == OP_SET:
                        vidx.insert(pk.uid, p.val().value)
                    elif p.op == OP_DEL:
                        vidx.remove(pk.uid)
        return commit_ts

    def _feed_stats(self, deltas):
        """Count index-key postings into the cm-sketch (ref posting/stats
        collection feeding planForEqFilter)."""
        from dgraph_tpu.utils.cmsketch import feed_stats

        feed_stats(self.stats, deltas)

    # -- mutations -------------------------------------------------------------

    def _apply_rdf(
        self, txn: Txn, set_rdf: str, del_rdf: str, ns: int = keys.GALAXY_NS
    ) -> Dict[str, str]:
        return self._apply_nquads(
            txn, parse_rdf(set_rdf), parse_rdf(del_rdf), ns
        )

    def _apply_rdf_with_vars(
        self, txn: Txn, set_rdf: str, del_rdf: str, uid_vars, val_vars,
        ns: int = keys.GALAXY_NS,
    ) -> Dict[str, str]:
        """RDF application where subjects/objects may be uid(v) refs and
        values val(v) refs; the mutation fans out over the var's uids
        (ref dql upsert semantics)."""
        blank: Dict[str, int] = {}

        def resolve_many(ref: str) -> List[int]:
            if ref.startswith("uid("):
                var = ref[4:-1]
                return uid_vars.get(var, [])
            if ref.startswith("_:"):
                if ref not in blank:
                    blank[ref] = self.zero.assign_uids(1)
                return [blank[ref]]
            return [int(ref, 16) if ref.startswith("0x") else int(ref)]

        def apply_all(rdf: str, op: int):
            for nq in parse_rdf(rdf):
                for subj in resolve_many(nq.subject):
                    if nq.object_id and nq.object_id.startswith("val("):
                        # val(v): per-subject value substitution
                        var = nq.object_id[4:-1]
                        v = val_vars.get(var, {}).get(subj)
                        if v is None:
                            continue
                        apply_edge(
                            txn,
                            self.schema,
                            DirectedEdge(
                                subj, nq.predicate, value=v,
                                facets=nq.facets, op=op, ns=ns,
                            ),
                        )
                        continue
                    objs = (
                        resolve_many(nq.object_id) if nq.object_id else [None]
                    )
                    for obj in objs:
                        self._apply_nquad(
                            txn, nq, None, op, subj_uid=subj, obj_uid=obj,
                            ns=ns,
                        )

        apply_all(set_rdf, OP_SET)
        apply_all(del_rdf, OP_DEL)
        return {k[2:]: hex(v) for k, v in blank.items()}

    def _nquad_edge(
        self,
        nq: NQuad,
        resolve,
        op: int,
        subj_uid: Optional[int] = None,
        obj_uid: Optional[int] = None,
        ns: int = keys.GALAXY_NS,
    ) -> DirectedEdge:
        """Build the DirectedEdge for one (non-star) N-Quad."""
        subj = subj_uid if subj_uid is not None else resolve(nq.subject)
        if nq.object_id:
            return DirectedEdge(
                subj,
                nq.predicate,
                value_id=obj_uid if obj_uid is not None else resolve(nq.object_id),
                facets=nq.facets,
                op=op,
                ns=ns,
            )
        return DirectedEdge(
            subj,
            nq.predicate,
            value=nq.object_value,
            lang=nq.lang,
            facets=nq.facets,
            op=op,
            ns=ns,
        )

    def _apply_nquad(
        self,
        txn: Txn,
        nq: NQuad,
        resolve,
        op: int,
        subj_uid: Optional[int] = None,
        obj_uid: Optional[int] = None,
        ns: int = keys.GALAXY_NS,
    ):
        """Apply one N-Quad. Callers either pass a `resolve` function or
        pre-resolved subject/object uids (the upsert fan-out path — pinned
        by role, so `uid(v) <p> uid(v)` self-pairs resolve correctly)."""
        if nq.star:
            if op != OP_DEL:
                raise ValueError("S P * only valid in delete")
            subj = subj_uid if subj_uid is not None else resolve(nq.subject)
            delete_entity_attr(txn, self.schema, subj, nq.predicate, ns)
            return
        edge = self._nquad_edge(
            nq, resolve, op, subj_uid=subj_uid, obj_uid=obj_uid, ns=ns
        )
        apply_edge(txn, self.schema, edge)

    def _apply_json(
        self, txn: Txn, set_obj, del_obj, ns: int = keys.GALAXY_NS
    ) -> Dict[str, str]:
        """JSON mutation format (ref chunker/json_parser.go): nested
        objects with "uid" refs; blank nodes via "_:name". Delegates to
        the var-aware walker (no vars bound) so set/delete semantics —
        schema-typed conversion, bare-uid node deletes, null-predicate
        deletes — stay in one place."""
        return self._apply_json_with_vars(txn, set_obj, del_obj, {}, ns=ns)

    def _node_type_preds(self, txn: Txn, uid: int, ns=keys.GALAXY_NS):
        """Predicates expanded from the node's dgraph.type definitions
        (ref worker/mutation.go expandEdges for S * * deletes)."""
        tkey = keys.DataKey("dgraph.type", uid, ns)
        preds = []
        for p in txn.cache.values(tkey):
            tu = self.schema.get_type(str(p.val().value))
            if tu is not None:
                preds.extend(tu.fields)
        return preds

    def _apply_json_with_vars(
        self, txn: Txn, set_obj, del_obj, uid_vars,
        ns: int = keys.GALAXY_NS, blank: Optional[Dict[str, int]] = None,
        val_vars: Optional[Dict[str, dict]] = None,
    ) -> Dict[str, str]:
        """JSON mutations whose uid refs may be upsert vars — the format
        the reference's GraphQL mutation rewriters emit (setjson /
        deletejson with "uid(x)" refs and @if conds, ref
        graphql/resolve/mutation_rewriter.go + edgraph doMutate var
        expansion). Values convert by schema type (geo dicts, datetimes),
        a bare {"uid": U} in delete drops the whole node (S * *), and a
        null field value in delete drops the predicate (S P *)."""
        blank = blank if blank is not None else {}

        fresh_uids: set = set()  # uids leased by THIS request

        def resolve_many(ref) -> List[int]:
            if isinstance(ref, int):
                return [ref]
            if ref.startswith("uid("):
                return list(uid_vars.get(ref[4:-1], []))
            if ref.startswith("_:"):
                if ref not in blank:
                    blank[ref] = self.zero.assign_uids(1)
                    fresh_uids.add(blank[ref])
                return [blank[ref]]
            return [int(ref, 16) if ref.startswith("0x") else int(ref)]

        def to_val(su, v) -> Val:
            # (geo dicts never reach here — walk() routes them through
            # is_geo_literal directly; `su` is the caller's schema
            # entry — one lookup per field, not one per item)
            tid = su.value_type if su is not None else None
            if tid == TypeID.DATETIME:
                from dgraph_tpu.types.types import parse_datetime

                return Val(TypeID.DATETIME, parse_datetime(str(v)))
            if tid == TypeID.PASSWORD:
                from dgraph_tpu.types.types import convert

                return convert(Val(TypeID.STRING, str(v)), TypeID.PASSWORD)
            if tid == TypeID.VFLOAT and isinstance(v, list):
                return Val(TypeID.VFLOAT, np.asarray(v, dtype=np.float32))
            return _json_to_val(v)

        def is_geo_literal(v) -> bool:
            return (
                isinstance(v, dict)
                and "coordinates" in v
                and v.get("type")
                in ("Point", "Polygon", "MultiPolygon", "MultiPoint")
            )

        # batched application: edges accumulate and flush through
        # apply_edges (bulk reads + bulk tokens, posting/mutation.py);
        # every delete flushes first so it observes the edges that
        # preceded it in walk order
        pending: List[DirectedEdge] = []

        def flush():
            if pending:
                apply_edges(txn, self.schema, pending)
                pending.clear()

        def edge(subj, pred, op, value=None, value_id=None, lang=""):
            pending.append(
                DirectedEdge(
                    subj, pred, value=value, value_id=value_id,
                    lang=lang, op=op, ns=ns,
                    fresh=subj in fresh_uids,
                )
            )

        def walk(obj, op, top=False) -> List[int]:
            uid_ref = obj.get("uid")
            subjects = resolve_many(
                uid_ref if uid_ref is not None else f"_:auto{id(obj)}"
            )
            rest = [(k, v) for k, v in obj.items() if k != "uid"]
            if op == OP_DEL and not rest and top:
                # bare top-level {"uid": U}: delete the node outright
                # (nested bare refs are edge targets, not node deletes)
                flush()
                for subj in subjects:
                    for pred in self._node_type_preds(txn, subj, ns):
                        delete_entity_attr(txn, self.schema, subj, pred, ns)
                    delete_entity_attr(
                        txn, self.schema, subj, "dgraph.type", ns
                    )
                return subjects
            schema_get = self.schema.get
            pending_append = pending.append
            for subj in subjects:
                fresh = subj in fresh_uids
                for k, v in rest:
                    if k == "dgraph.type":
                        for t in _as_list(v):
                            edge(
                                subj, "dgraph.type", op,
                                value=Val(TypeID.STRING, t),
                            )
                        continue
                    pred, lang = (
                        k.split("@", 1) if "@" in k else (k, "")
                    )
                    if v is None:
                        if op == OP_DEL:
                            flush()
                            delete_entity_attr(
                                txn, self.schema, subj, pred, ns
                            )
                        continue
                    su = schema_get(pred)
                    # flat-scalar fast path: the dominant live-loader
                    # shape is {"pred": <str|int|float|bool>} — one
                    # constructor each, skipping the list/geo/dict
                    # dispatch below (per-edge GIL work on the write
                    # hot path). DATETIME/PASSWORD convert in to_val.
                    tv = type(v)
                    if tv is str:
                        if not v.startswith("val(") and (
                            su is None
                            or su.value_type not in _SLOW_JSON_TIDS
                        ):
                            pending_append(DirectedEdge(
                                subj, pred, Val(TypeID.STRING, v),
                                None, lang, None, op, ns, fresh,
                            ))
                            continue
                    elif tv is bool or tv is int or tv is float:
                        if (
                            su is None
                            or su.value_type not in _SLOW_JSON_TIDS
                        ):
                            pending_append(DirectedEdge(
                                subj, pred,
                                Val(
                                    TypeID.BOOL if tv is bool
                                    else TypeID.INT if tv is int
                                    else TypeID.FLOAT, v,
                                ),
                                None, lang, None, op, ns, fresh,
                            ))
                            continue
                    if (
                        su is not None
                        and su.value_type == TypeID.VFLOAT
                        and isinstance(v, list)
                        and v
                        and isinstance(v[0], (int, float))
                    ):
                        edge(subj, pred, op, value=to_val(su, v))
                        continue
                    for item in _as_list(v):
                        if is_geo_literal(item):
                            edge(subj, pred, op, value=Val(TypeID.GEO, item))
                        elif isinstance(item, dict):
                            if len(item) == 1 and "uid" in item:
                                # bare nested ref: resolve without the
                                # recursive walk frame
                                for child in resolve_many(item["uid"]):
                                    pending_append(DirectedEdge(
                                        subj, pred, None, child, "",
                                        None, op, ns, fresh,
                                    ))
                                continue
                            for child in walk(item, op):
                                edge(subj, pred, op, value_id=child)
                        elif (
                            isinstance(item, str)
                            and item.startswith("val(")
                            and item.endswith(")")
                        ):
                            # val(v): per-subject value substitution,
                            # like the RDF upsert path
                            vv = (val_vars or {}).get(item[4:-1], {})
                            got = vv.get(subj)
                            if got is not None:
                                edge(subj, pred, op, value=got, lang=lang)
                        else:
                            edge(
                                subj, pred, op,
                                value=to_val(su, item), lang=lang,
                            )
            return subjects

        for obj in _as_list(set_obj):
            walk(obj, OP_SET, top=True)
        for obj in _as_list(del_obj):
            walk(obj, OP_DEL, top=True)
        flush()
        return {k[2:]: hex(v) for k, v in blank.items()}

    # -- observability ----------------------------------------------------------

    def health(self) -> dict:
        """Single-node health/SLO rollup (/debug/healthz body): the
        process healthz (admission rates, pipeline depth, SLO burn
        windows) plus this engine's snapshot-watermark lag. No raft
        groups here — the cluster engines report those."""
        from dgraph_tpu.utils import observe

        out = observe.healthz("alpha")
        out["snapshot_watermark"] = int(self._snapshot_ts)
        ma = getattr(self.zero, "max_assigned", None)
        if isinstance(ma, (int, float)):
            out["watermark_lag"] = max(0, int(ma) - self._snapshot_ts)
        return out

    # -- queries ----------------------------------------------------------------

    def _plan_cache_tiers(self) -> Dict[str, float]:
        from dgraph_tpu.posting.lists import cache_tier_snapshot

        return cache_tier_snapshot(self.mem)

    def query(
        self,
        q: str,
        read_ts: Optional[int] = None,
        access_jwt: Optional[str] = None,
        variables: Optional[Dict[str, str]] = None,
        timeout_ms: Optional[float] = None,
        want: str = "dict",
        debug: bool = False,
    ) -> dict:
        """Run a read-only query at a fresh (or given) read ts.
        timeout_ms bounds execution (ref x/limits --query timeout).
        The response carries reference-shaped extensions.server_latency
        plus the per-query profile; slow queries are force-sampled and
        appended to the slow-query JSONL log (DGRAPH_TPU_SLOW_QUERY_MS,
        DGRAPH_TPU_SLOW_QUERY_LOG).

        `want="raw"` skips the dict-API parse-back: `data` comes back
        as a streamjson.RawJson byte shell for response assembly to
        splice (the HTTP/gRPC serving surface).

        `debug=True` (EXPLAIN/ANALYZE — HTTP ?debug=true, gRPC
        Request.vars["debug"]) turns on the decision-capture hooks and
        attaches the structured plan tree as `extensions.plan`. Capture
        is observation-only: response `data` bytes are identical with
        the flag on or off (golden-enforced, tests/test_explain.py)."""
        # the `query` span opens before anything else: `parse` and
        # `admit` are its children, as `process` and `encode` are
        with observe.TRACER.span("query", cpu=True) as root:
            return self._query_spanned(
                root, q, read_ts, access_jwt, variables, timeout_ms, want,
                debug,
            )

    def _query_spanned(
        self, root, q, read_ts, access_jwt, variables, timeout_ms, want,
        debug,
    ) -> dict:
        import time as _time

        from dgraph_tpu.utils.observe import METRICS, TRACER, profile_scope

        t_begin = _time.monotonic()
        # info is now always collected: the digest store records the
        # plan-cache outcome per shape, not just EXPLAIN requests (the
        # fill is three dict writes — observation-only either way)
        parse_info: dict = {}
        digested = False  # one digest record per query, on every path
        try:
            # plan cache: repeated query shapes skip parse entirely
            with TRACER.span("parse", cpu=True, fine=True) as sp:
                blocks, shape, literals = self.serving.parse(
                    q, variables, info=parse_info
                )
                sp.attrs["plan_cache_hit"] = bool(parse_info.get("hit"))
        except Exception:
            # unparseable queries accrue to the per-ns `other` bucket —
            # a flood of malformed text is an operator-visible shape
            if DIGESTS.enabled():
                DIGESTS.record(
                    keys.GALAXY_NS, None,
                    _time.monotonic() - t_begin, error=True,
                )
            raise
        t_parsed = _time.monotonic()
        # admission gate BEFORE the read-ts allocation: a shed must be
        # FAST and side-effect-free — under overload the oracle's
        # applied-barrier wait is exactly where queries queue, and a
        # request that will be refused must neither join that queue
        # nor lease a timestamp
        # `admit`, in two stretches around the `try`: the admission
        # gate here; ACL, audit and the read ts below
        with TRACER.span("admit", cpu=True, fine=True) as sp:
            ticket = self.serving.admit(shape, blocks)
            sp.attrs["degrade"] = bool(ticket.degrade)
        slow = False
        completed = False  # clean, untruncated execution
        try:
            with TRACER.span("admit", cpu=True, fine=True):
                ns = keys.GALAXY_NS
                allowed = None
                user = ""
                if self.acl is not None:
                    from dgraph_tpu.acl.acl import READ, AclError

                    try:
                        if access_jwt is None:
                            raise AclError("no access token (ACL enabled)")
                        claims = self.acl.claims(access_jwt)
                        user = claims.get("userid", "")
                        ns = int(claims.get("namespace", 0))
                        self.acl.authorize_preds(
                            access_jwt, _query_preds(blocks), READ,
                            claims=claims,
                        )
                        allowed = self.acl.readable_preds(claims)
                    except Exception:
                        self._audit("query", user=user, body=q, status="DENIED")
                        raise
                self._audit("query", user=user, ns=ns, body=q)
                from dgraph_tpu.query.functions import QueryBudgetError

                deadline = (
                    _time.monotonic() + timeout_ms / 1e3
                    if timeout_ms is not None
                    else None
                )
                degrade_deadline = None
                if ticket.degrade:
                    # saturated: run under a bounded budget and return a
                    # partial/degraded response on exhaustion instead of
                    # queueing at full budget (PR 3's partial-result shape)
                    degrade_deadline = (
                        _time.monotonic() + self.serving.degrade_budget_s()
                    )
                    deadline = (
                        degrade_deadline
                        if deadline is None
                        else min(deadline, degrade_deadline)
                    )
                truncated = False
                # snapshot-watermark read (ref worker/oracle MaxAssigned):
                # `_snapshot_ts` is published only after a commit's deltas
                # are written, and advances in commit-ts order — so a read
                # AT the watermark sees a complete store without leasing a
                # fresh ts and waiting out the apply barrier. Under mixed
                # traffic that wait serialized every read behind the write
                # pipeline's in-flight window; an in-flight (unacked)
                # commit is legitimately excluded from the snapshot. 0 =
                # nothing committed yet: fall back to a fresh barrier-
                # waited lease.
                # the watermark is sampled ONCE and reused for BOTH the
                # read ts and the result-cache key: re-reading
                # _snapshot_ts at key time would let a commit landing in
                # between cache watermark-N bytes under the watermark-N+1
                # key (a one-line TOCTOU that breaks the never-stale
                # proof)
                wm = self._snapshot_ts
                ts = (
                    read_ts
                    if read_ts is not None
                    else (wm or self.zero.read_ts())
                )
                t_assigned = _time.monotonic()
            # snapshot-keyed result reuse (serving/resultcache.py):
            # watermark reads with no ACL are a pure function of
            # (shape, literals, vars, ns, watermark) — the PR 7/11
            # proof — so the whole response's wire bytes can be
            # served from the LRU. Caller-pinned read_ts never
            # caches; EXPLAIN queries always execute but record the
            # would-hit tier in the plan.
            rc_key = None
            rc_probe = False
            raw_hit = None
            if read_ts is None and self.acl is None:
                rc_key, raw_hit, rc_probe = self.serving.result_probe(
                    shape, literals, variables, ns, wm, debug,
                )
            if raw_hit is not None:
                from dgraph_tpu.serving.resultcache import hit_response

                METRICS.inc("num_queries")
                t_done = _time.monotonic()
                # hits are SERVED traffic: they must land in the
                # latency histogram the SLO/health surface reads. The
                # sample is the PROCESSING span (post-assign), the
                # same span the miss path's METRICS.timer covers — a
                # hit recording full wall time would make hit samples
                # incomparable with miss samples in one histogram
                METRICS.observe(
                    "query_latency_seconds", t_done - t_assigned
                )
                # shape stays out of the cost EWMA (finally passes
                # shape only when `completed`): a hit's latency
                # describes the cache, not the shape's execution cost
                # the admission gate estimates
                if DIGESTS.enabled():
                    DIGESTS.record(
                        ns, shape, t_done - t_begin,
                        nbytes=len(raw_hit),
                        plan_hit=bool(parse_info.get("hit")),
                        result_hit=True,
                    )
                    digested = True
                observe.note_tenant("query", ns, t_done - t_assigned)
                return hit_response(
                    raw_hit, want,
                    parsing_ns=int((t_parsed - t_begin) * 1e9),
                    assign_ns=int((t_assigned - t_parsed) * 1e9),
                    processing_ns=int((t_done - t_assigned) * 1e9),
                    watermark=wm,
                )
            cache_base = self._plan_cache_tiers() if debug else None
            root.attrs["ns"] = ns
            with profile_scope(debug=debug) as prof, \
                    METRICS.timer("query_latency_seconds"):
                try:
                    cache = LocalCache(self.kv, ts, mem=self.mem)
                    # caller-pinned read_ts never coalesces: the
                    # snapshot-watermark argument only covers fresh
                    # engine-allocated timestamps (which waited on the
                    # applied barrier)
                    out = self._query_parsed(
                        blocks,
                        cache,
                        ns,
                        allowed,
                        deadline=deadline,
                        batcher=(
                            self.serving.batcher_for(cache)
                            if read_ts is None
                            else None
                        ),
                        want=want,
                    )
                except QueryBudgetError:
                    # only the degraded-admission budget converts a
                    # deadline trip into a partial result; semantic
                    # errors (different type) and a tighter CLIENT
                    # timeout (trips before the degrade budget) raise
                    if (
                        degrade_deadline is None
                        or _time.monotonic() < degrade_deadline
                    ):
                        raise
                    out = {"data": {}}
                    truncated = True
            METRICS.inc("num_queries")
            t_done = _time.monotonic()
            took_ms = (t_done - t_begin) * 1e3
            ext = out.setdefault("extensions", {})
            # encoding happens inside _query_parsed; it reports the
            # wire-bytes production time through the profile and the
            # processing component gives it up so the parts still sum
            # to total_ns with no unattributed gap (the dict-API
            # parse-back, when present, stays inside processing and is
            # itemized as profile.encode.parse_ns)
            enc_ns = int(prof.encode.get("encode_ns", 0))
            total_ns = int((t_done - t_begin) * 1e9)
            ext["server_latency"] = {
                # new order: parse -> admission/ACL/ts -> execute; the
                # admission + ACL + audit time rides in the assign
                # component
                "parsing_ns": int((t_parsed - t_begin) * 1e9),
                "assign_timestamp_ns": int((t_assigned - t_parsed) * 1e9),
                "processing_ns": max(
                    int((t_done - t_assigned) * 1e9) - enc_ns, 0
                ),
                "encoding_ns": enc_ns,
                "total_ns": total_ns,
            }
            if total_ns > 0 and prof.encode:
                prof.encode["share"] = round(enc_ns / total_ns, 4)
            ext["profile"] = prof.to_dict()
            if prof.plan is not None:
                prof.plan.plan_cache = parse_info or {}
                prof.plan.admission = {
                    "enabled": self.serving.admission.enabled(),
                    "cost": round(ticket.cost, 3),
                    "degrade": ticket.degrade,
                }
                if cache_base is not None:
                    now_tiers = self._plan_cache_tiers()
                    prof.plan.cache = {
                        k: now_tiers[k] - cache_base.get(k, 0)
                        for k in now_tiers
                    }
                prof.plan.result_cache = {
                    "enabled": self.serving.results.capacity() > 0,
                    "eligible": rc_key is not None,
                    "would_hit": bool(rc_probe),
                    "watermark": int(self._snapshot_ts),
                }
                prof.plan.meta = {
                    "read_ts": int(ts),
                    "snapshot_watermark": int(self._snapshot_ts),
                    "wall_ns": total_ns,
                }
                ext["plan"] = prof.plan.to_dict()
            if root.trace_id:
                ext["trace_id"] = f"{root.trace_id:032x}"
            if ticket.degrade:
                ext["degraded_admission"] = True
            if truncated:
                METRICS.inc("degraded_queries_total")
                ext["degraded"] = True
                ext["partial"] = True
            if DIGESTS.enabled():
                data = out.get("data")
                rows = (
                    sum(
                        len(v)
                        for v in data.values()
                        if isinstance(v, list)
                    )
                    if isinstance(data, dict)
                    else 0
                )
                DIGESTS.record(
                    ns, shape, t_done - t_begin,
                    rows=rows,
                    nbytes=int(prof.encode.get("bytes", 0)),
                    error=truncated,
                    plan_hit=bool(parse_info.get("hit")),
                    setop_pairs=int(
                        prof.events.get("setop_pairs_total", 0)
                    ),
                    setop_packed=int(
                        prof.events.get("setop_packed_total", 0)
                    ),
                )
                digested = True
            observe.note_tenant("query", ns, t_done - t_assigned)
            # structured slow-query log (ref x/log.go LogSlowOperation,
            # edgraph/server.go:1448): force-sample + bounded JSONL —
            # the digest shape key rides along so a slow entry joins
            # its aggregate row in /debug/digests
            slow = observe.maybe_log_slow(
                "query", q, took_ms, root,
                extra={"ns": ns, "shape": shape},
                threshold_ms=self.slow_query_ms,
            )
            completed = not truncated
            if rc_key is not None and completed:
                raw = getattr(out.get("data"), "raw", None)
                if raw is not None:
                    self.serving.results.put(rc_key, raw)
            return out
        finally:
            # a query that entered execution but never reached a digest
            # record (ACL denial, semantic error, client deadline)
            # still counts against its shape — errors are a first-class
            # digest column
            if not digested and DIGESTS.enabled():
                DIGESTS.record(
                    ns, shape, _time.monotonic() - t_begin, error=True,
                )
            # only clean completions feed the shape cost EWMA: a
            # truncated/denied/failed run's latency describes the
            # failure, not the shape
            self.serving.finish(
                ticket,
                shape if completed else None,
                (_time.monotonic() - t_begin) * 1e3,
                slow=slow,
            )

    def query_rdf(
        self,
        q: str,
        read_ts: Optional[int] = None,
        variables: Optional[Dict[str, str]] = None,
    ) -> str:
        """Query with RDF (N-Quads) response encoding (ref
        query/outputrdf.go ToRDF; resp_format=RDF on the wire)."""
        from dgraph_tpu.query.outputrdf import encode_rdf

        ts = read_ts if read_ts is not None else self.zero.read_ts()
        blocks = dql.parse(q, variables)
        ex = Executor(
            LocalCache(self.kv, ts, mem=self.mem),
            self.schema,
            vector_indexes=self.vector_indexes,
        )
        nodes = ex.process(blocks)
        return encode_rdf(nodes)

    def _query(self, q: str, cache: LocalCache) -> dict:
        return self._query_parsed(dql.parse(q), cache, keys.GALAXY_NS)

    def _schema_query(self, gq) -> dict:
        """schema {} / schema(pred: ...) / schema(type: ...) blocks
        (ref dql parseSchema + worker schema retrieval; golden shapes in
        query0_test.go TestSchemaBlock*)."""
        from dgraph_tpu.types.types import type_name as _tn

        if gq.expand:  # schema(type: A) / schema(type: [A, B])
            types = []
            for tname in sorted(gq.expand.split(",")):
                tu = self.schema.get_type(tname)
                if tu is not None:
                    types.append(
                        {
                            "name": tu.name,
                            "fields": [{"name": f} for f in tu.fields],
                        }
                    )
            return {"data": {"types": types} if types else {}}
        want = set(gq.facet_names)  # requested fields ({} = all)
        preds = gq.groupby_attrs or sorted(self.schema.predicates())
        out = []
        for pred in preds:
            su = self.schema.get(pred)
            if su is None:
                continue  # unknown preds silently dropped (ref behavior)
            row: dict = {"predicate": pred}

            def put(field, value, truthy=True):
                if want and field not in want:
                    return
                if truthy and not value:
                    return
                row[field] = value

            put("type", _tn(su.value_type), truthy=False)
            put("index", bool(su.directive_index))
            if su.directive_index:
                put("tokenizer", list(su.tokenizers))
            put("reverse", su.directive_reverse)
            put("count", su.count)
            put("lang", su.lang)
            put("list", su.is_list)
            put("upsert", su.upsert)
            put("unique", su.unique)
            put("no_conflict", su.no_conflict)
            out.append(row)
        return {"data": {"schema": out}}

    def _query_parsed(
        self,
        blocks,
        cache: LocalCache,
        ns: int,
        allowed_preds=None,
        deadline=None,
        batcher=None,
        want: str = "dict",
    ) -> dict:
        if len(blocks) == 1 and blocks[0].attr == "__schema__":
            return self._schema_query(blocks[0])
        ex = Executor(
            cache,
            self.schema,
            ns=ns,
            vector_indexes=self.vector_indexes,
            allowed_preds=allowed_preds,
            stats=self.stats,
            deadline=deadline,
            batcher=batcher,
        )
        with observe.TRACER.span("process", cpu=True, fine=True) as sp:
            nodes = ex.process(blocks)
            if ex.order_tally:
                sp.attrs["order_cands"] = ex.order_tally[
                    "order_candidates_total"]
                sp.attrs["order_kept"] = ex.order_tally["order_kept_total"]
                sp.attrs["order_buckets"] = ex.order_tally[
                    "order_buckets_total"]
            if ex.uid_ids:
                sp.attrs["uid_ids"] = ex.uid_ids
            if ex.column_tally:
                sp.attrs["column_cands"] = ex.column_tally["cands"]
                sp.attrs["column_kept"] = ex.column_tally["kept"]
        with observe.TRACER.span("encode", cpu=True, fine=True) as sp:
            data, enc_stats = encode_response_data(
                nodes, val_vars=ex.val_vars, schema=self.schema, want=want
            )
            sp.attrs["bytes"] = int(enc_stats.get("bytes", 0))
        prof = observe.current_profile()
        if prof is not None:
            prof.encode.update(enc_stats)
            if prof.plan is not None:
                prof.plan.planner = (
                    ex.planner.explain()
                    if ex.planner is not None
                    else {"enabled": False}
                )
        return {"data": data}


def _query_preds(blocks) -> list:
    """All predicates a query touches (for ACL checks,
    ref edgraph/server.go authorizeRequest)."""
    preds = set()

    def from_func(fn):
        if fn is None or not fn.attr:
            return
        if fn.name == "type":
            preds.add("dgraph.type")  # attr holds the type NAME, not a pred
        else:
            preds.add(fn.attr.lstrip("~"))

    def from_filter(ft):
        if ft is None:
            return
        from_func(ft.func)
        for c in ft.children:
            from_filter(c)

    def walk(g):
        from_func(g.func)
        from_filter(g.filter)
        # classify by node kind (flags), not by attr-name heuristics — a
        # data predicate literally named "q"/"var" must still be checked
        is_virtual = (
            g.is_uid
            or g.val_var
            or g.aggregator
            or g.math_expr is not None
            or g.expand  # expanded preds are ACL-filtered at execution
            or (g.is_count and g.attr == "uid")
        )
        if g.attr and not is_virtual:
            preds.add(g.attr.lstrip("~"))
        for ga in g.groupby_attrs:
            preds.add(ga.lstrip("~"))
        for o in g.order:
            if o.attr:
                preds.add(o.attr)
        for c in g.children:
            walk(c)

    for b in blocks:
        for c in b.children:
            walk(c)
        from_func(b.func)
        from_filter(b.filter)
        for ga in b.groupby_attrs:
            preds.add(ga.lstrip("~"))
        for o in b.order:
            if o.attr:
                preds.add(o.attr)
    return sorted(preds)


def _json_preds(obj) -> set:
    """Predicates referenced by a JSON mutation object tree."""
    preds = set()

    def walk(o):
        if isinstance(o, list):
            for it in o:
                walk(it)
            return
        if not isinstance(o, dict):
            return
        for k, v in o.items():
            if k == "uid":
                continue
            preds.add(k.split("@", 1)[0])
            if isinstance(v, (dict, list)):
                walk(v)

    walk(obj)
    return preds


def _eval_cond(cond: str, uid_vars) -> bool:
    """Evaluate '@if(...)' upsert conditions: len(var) comparisons
    combined with AND/OR/NOT and parentheses (ref dql conditional
    mutations, edgraph/server.go parseMutationObject cond handling)."""
    import re as _re

    m = _re.match(r"\s*@if\s*\((.*)\)\s*$", cond, _re.S)
    if not m:
        raise ValueError(f"unsupported upsert condition {cond!r}")
    expr = m.group(1)

    tokens = _re.findall(
        r"\(|\)|AND\b|OR\b|NOT\b|and\b|or\b|not\b|"
        r"(?:eq|lt|le|gt|ge)\s*\(\s*len\s*\(\s*\w+\s*\)\s*,\s*\d+\s*\)",
        expr,
    )
    if not tokens or "".join(tokens).replace(" ", "") != expr.replace(" ", ""):
        raise ValueError(f"unsupported upsert condition {cond!r}")
    pos = 0

    def atom(tok: str) -> bool:
        am = _re.match(
            r"(eq|lt|le|gt|ge)\s*\(\s*len\s*\(\s*(\w+)\s*\)\s*,\s*(\d+)\s*\)",
            tok,
        )
        op, var, n = am.group(1), am.group(2), int(am.group(3))
        ln = len(uid_vars.get(var, []))
        return {
            "eq": ln == n,
            "lt": ln < n,
            "le": ln <= n,
            "gt": ln > n,
            "ge": ln >= n,
        }[op]

    def parse_or() -> bool:
        nonlocal pos
        left = parse_and()
        while pos < len(tokens) and tokens[pos].lower() == "or":
            pos += 1
            right = parse_and()
            left = left or right
        return left

    def parse_and() -> bool:
        nonlocal pos
        left = parse_not()
        while pos < len(tokens) and tokens[pos].lower() == "and":
            pos += 1
            right = parse_not()
            left = left and right
        return left

    def parse_not() -> bool:
        nonlocal pos
        if pos < len(tokens) and tokens[pos].lower() == "not":
            pos += 1
            return not parse_not()
        return parse_primary()

    def parse_primary() -> bool:
        nonlocal pos
        tok = tokens[pos]
        if tok == "(":
            pos += 1
            v = parse_or()
            if pos >= len(tokens) or tokens[pos] != ")":
                raise ValueError(f"unbalanced parens in {cond!r}")
            pos += 1
            return v
        pos += 1
        return atom(tok)

    out = parse_or()
    if pos != len(tokens):
        raise ValueError(f"trailing tokens in upsert condition {cond!r}")
    return out


# schema value types whose JSON scalars need to_val's conversion work
# (everything else takes the flat-scalar fast path in the JSON walker)
_SLOW_JSON_TIDS = (TypeID.DATETIME, TypeID.PASSWORD)


def _as_list(x):
    if x is None:
        return []
    return x if isinstance(x, list) else [x]


def _json_to_val(item) -> Val:
    if isinstance(item, bool):
        return Val(TypeID.BOOL, item)
    if isinstance(item, int):
        return Val(TypeID.INT, item)
    if isinstance(item, float):
        return Val(TypeID.FLOAT, item)
    if isinstance(item, list):
        return Val(TypeID.VFLOAT, np.asarray(item, dtype=np.float32))
    return Val(TypeID.STRING, str(item))
