"""Replica — one object owning a replica's full merge lifecycle.

Before this facade, running a replica meant hand-wiring five parts:
`CRDTMergeState` (Layer 1), the blob store riding inside it, the
process-global engine cache, an optional `TrustState`, and a
`SyncNode.fetch_hook` for sharded stores. `Replica` owns all of them:

    rep = Replica("inst-a")
    eid = rep.contribute(fine_tune)
    rep.merge(other_rep)                       # CRDT join
    rep.report(bad_eid, "statistical_outlier")
    merged = rep.resolve(MergeSpec("ties", {"trim": 0.3},
                                   trust_threshold=0.5))

Every resolve goes through `core.resolve.resolve_spec`, i.e. the
planner/executor engine — including trust-gated and hierarchical
(`group_size`) specs — with THIS replica's `EngineCache`: two replicas
in one process no longer alias each other's LRU order, byte budget, or
hit/miss counters.

`attach(sync_node)` hands state ownership to a `repro.net.SyncNode`:
contributions/retractions flow through the node (so its partial-blob
bookkeeping stays coherent), and resolves pull non-resident payloads
through the node's fetch hook — the facade over a sharded,
anti-entropy-synced deployment.

`Replica(path=...)` makes the replica durable (repro.core.journal):
the directory's blob log + Layer-1 WAL replay on open — restart
recovers the exact pre-crash Merkle root and every locally-held blob
with zero network bytes — and every subsequent operation is recorded
before it is acknowledged. `close()` flushes and releases the storage
(idempotent); `with Replica(path=...) as rep:` scopes it.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

import jax

from repro.api.spec import MergeSpec
from repro.core.engine import CacheInfo, EngineCache, seed_meta
from repro.core.hashing import (digest_workers, pytree_leaf_digests,
                                tree_nbytes)
from repro.core.state import CRDTMergeState
from repro.core.trust import TrustState
from repro.obs import MetricsRegistry, span

__all__ = ["Replica"]


class Replica:
    """Facade over state + store + per-replica cache + trust + sync."""

    def __init__(self, node_id: str = "local", *,
                 state: Optional[CRDTMergeState] = None,
                 trust: Optional[TrustState] = None,
                 cache: Optional[EngineCache] = None,
                 obs: Optional[MetricsRegistry] = None,
                 path: Optional[str] = None):
        self.node_id = node_id
        self._state = state if state is not None else CRDTMergeState()
        self.trust = trust
        # per-replica telemetry scope: a fresh cache shares the
        # replica's registry, so engine counters surface through
        # metrics(); an injected cache keeps its own (its owner may
        # already be watching it — metrics() merges both).
        self.obs = obs if obs is not None else MetricsRegistry()
        self.cache = cache if cache is not None else EngineCache(
            obs=self.obs)
        self._bases: Dict[str, Any] = {}
        self._node = None                  # attached repro.net.SyncNode
        self._storage = None               # repro.core.journal.DurableStore
        self._closed = False
        if path is not None:
            from repro.core.journal import DurableStore
            self._storage = DurableStore(path, obs=self.obs)
            recovered = self._storage.load()
            merged = recovered.merge(self._state)
            if merged != recovered \
                    or merged.store.keys() != recovered.store.keys():
                self._storage.record_transition(recovered, merged)
            self._state = merged

    # ----------------------------------------------------------- state

    @property
    def state(self) -> CRDTMergeState:
        return self._node.state if self._node is not None else self._state

    @state.setter
    def state(self, value: CRDTMergeState) -> None:
        if self._node is not None:
            self._node.state = value
        else:
            self._set_state(value)

    def _set_state(self, value: CRDTMergeState) -> None:
        """Unattached write path: durable write-through when a storage
        directory is open (attached, the node's own setter records)."""
        if self._storage is not None and value is not self._state:
            self._storage.record_transition(self._state, value)
        self._state = value

    def contribute(self, contribution: Any,
                   element_id: Optional[str] = None, *,
                   leaves: Optional[Iterable[str]] = None) -> str:
        """Publish a model contribution; returns its element id (the
        content hash that names it everywhere — ordering, Merkle roots,
        blob fetch, retraction).

        `leaves` declares a SPARSE contribution: the pytree is partial,
        carrying exactly the listed leaf paths (canonical `keystr`
        form, e.g. `"['a']['kernel']"`). At resolve time each model
        leaf merges over only the contributions covering it; a leaf
        covered by no contribution inherits the base model verbatim
        (Remark-16 reference semantics — the choice is part of every
        cache key). Pass the pytree's own paths (`leaf_paths_of`) or
        let validation catch a mismatch."""
        with span("layer1.contribute") as sp:
            eid, leaf_digests = element_id, None
            if not eid:
                eid, leaf_digests = self._content_id(contribution,
                                                     "contribute")
            sp.set(eid=eid[:16])
            if self._node is not None:
                self._node.contribute(contribution, element_id=eid,
                                      leaves=leaves)
            else:
                self._set_state(self._state.add(contribution, self.node_id,
                                                element_id=eid,
                                                leaf_paths=leaves))
            if leaf_digests is not None:
                # the planner takes the leaf digests the id was made of
                seed_meta(contribution, eid, leaf_digests, obs=self.obs)
        return eid

    def _content_id(self, tree: Any,
                    site: str) -> Tuple[str, Dict[str, bytes]]:
        """Content id of `tree` (hex SHA-256) and the digests of its
        leaves by path, spanned as `layer1.digest` and counted under
        `hash_bytes_total{site}` on this replica's registry, and under
        `hash_pooled_leaves_total{site}` where the leaves were hashed
        on the digest pool."""
        nbytes = tree_nbytes(tree)
        n_leaves = len(jax.tree_util.tree_leaves(tree))
        workers = digest_workers(n_leaves)
        with span("layer1.digest", bytes=nbytes, leaves=n_leaves,
                  workers=workers) as sp:
            digest, leaf_digests = pytree_leaf_digests(tree)
            ref = digest.hex()
            sp.set(eid=ref[:16])
        self.obs.counter("hash_bytes_total").inc(nbytes, site=site)
        if workers > 1:
            self.obs.counter("hash_pooled_leaves_total").inc(n_leaves,
                                                             site=site)
        return ref, leaf_digests

    def add(self, contribution: Any, *,
            leaves: Optional[Iterable[str]] = None,
            element_id: Optional[str] = None) -> str:
        """Alias of `contribute` with the sparse-first signature:
        `replica.add(delta, leaves=leaf_paths_of(delta))`."""
        return self.contribute(contribution, element_id, leaves=leaves)

    def retract(self, element_id: str) -> None:
        """OR-Set remove: tombstone every observed tag of the element."""
        with span("layer1.retract", eid=element_id[:16]):
            if self._node is not None:
                self._node.retract(element_id)
            else:
                self._set_state(self._state.remove(element_id,
                                                   self.node_id))

    def merge(self, other: Any) -> "Replica":
        """CRDT join with another Replica, a raw CRDTMergeState, or an
        attached node's state. Trust evidence joins too (it is itself a
        grow-only CRDT). Returns self for chaining."""
        if isinstance(other, Replica):
            state, trust = other.state, other.trust
        elif isinstance(other, CRDTMergeState):
            state, trust = other, None
        else:
            raise TypeError(f"cannot merge {type(other).__name__}")
        if self._node is not None:
            self._node.join(state)
        else:
            self._set_state(self._state.merge(state))
        if trust is not None:
            self.trust = trust if self.trust is None \
                else self.trust.merge(trust)
        return self

    def visible(self):
        return self.state.visible()

    def merkle_root(self) -> bytes:
        return self.state.merkle_root()

    # ----------------------------------------------------------- trust

    def report(self, element_id: str, kind: str,
               reporter: Optional[str] = None,
               severity: float = 1.0) -> "Replica":
        """File trust evidence against a contribution (grow-only CRDT;
        evidence merges with merge())."""
        base = self.trust if self.trust is not None else TrustState()
        self.trust = base.report(element_id, kind,
                                 reporter or self.node_id, severity)
        return self

    # ------------------------------------------------------------ base

    def register_base(self, payload: Any) -> str:
        """Pin a base model; returns its content ref for
        `MergeSpec(base_ref=...)`. Content-addressed: the ref fully
        determines the bytes, so specs carrying it are portable."""
        ref, _ = self._content_id(payload, "register_base")
        self._bases[ref] = payload
        return ref

    # --------------------------------------------------------- resolve

    def resolve(self, spec: MergeSpec, *, base: Any = None,
                use_cache: bool = True) -> Any:
        """Layer-2 resolve of `spec` over this replica's converged
        visible set — through the planner/executor engine with this
        replica's cache, gated by this replica's trust state when the
        spec asks, fetching non-resident payloads through the attached
        node's hook (leaf-granular: warm re-resolves fetch nothing)."""
        if not isinstance(spec, MergeSpec):
            raise TypeError(
                "Replica.resolve() takes a MergeSpec — e.g. "
                f"MergeSpec({spec!r}) — not {type(spec).__name__}")
        from repro.core.resolve import resolve_spec
        verify_base = True
        if base is None and spec.base_ref is not None:
            try:
                base = self._bases[spec.base_ref]
            except KeyError:
                raise KeyError(
                    f"base_ref {spec.base_ref[:16]}… not registered on "
                    "this replica; call register_base(payload) first"
                    ) from None
            # registry entries are keyed by their digest at
            # register_base time — re-hashing a multi-GB base on every
            # (possibly warm, zero-work) resolve would be pure waste
            verify_base = False
        return resolve_spec(self.state, spec, base=base,
                            trust=self.trust, fetch=self._fetch_hook(),
                            cache=self.cache, use_cache=use_cache,
                            verify_base=verify_base)

    def _fetch_hook(self):
        # the node's counted wrapper, so Replica-routed and node-routed
        # resolves account blob pulls identically
        return self._node._counted_fetch() if self._node is not None \
            else None

    # ------------------------------------------------------------ sync

    def attach(self, node: Any) -> "Replica":
        """Hand state ownership to a `repro.net.SyncNode`: the node's
        state absorbs this replica's, and from here on contribute /
        retract / merge / resolve all operate through the node (blob
        bookkeeping, placement filtering, fetch-on-resolve)."""
        if self._node is not None:
            raise RuntimeError("already attached; detach() first")
        if self._storage is not None and hasattr(node, "attach_storage"):
            # storage follows the state: the node's write-through takes
            # over recording (attach_storage joins the recovered state,
            # so node.join(self._state) below is a no-op on disk)
            storage, self._storage = self._storage, None
            node.attach_storage(storage)
        node.join(self._state)
        self._node = node
        return self

    def detach(self) -> "Replica":
        """Take the state (and any durable storage handed over by
        attach) back from the attached node."""
        if self._node is None:
            raise RuntimeError("not attached")
        self._state = self._node.state
        if self._storage is None and getattr(self._node, "storage", None) \
                is not None:
            self._storage = self._node.release_storage()
        self._node = None
        return self

    @property
    def node(self):
        return self._node

    # ------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Flush and release every owned resource — the durable storage
        (directly held or handed to an attached node) and the attached
        node's transfer bookkeeping. Idempotent; the replica stays
        readable (state/merkle_root) but must not be written again when
        durable. Reopen with `Replica(path=...)` to resume."""
        if self._closed:
            return
        if self._node is not None:
            if hasattr(self._node, "close"):
                self._node.close()
            self._state = self._node.state
            self._node = None
        if self._storage is not None:
            self._storage.close()
            self._storage = None
        self._closed = True

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "Replica":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------- cache

    def set_cache_limit(self, entries: Optional[int] = None, *,
                        bytes: Optional[int] = None) -> None:  # noqa: A002
        """Bound THIS replica's merge-output cache (entry count and/or
        resident bytes; LRU eviction applies immediately)."""
        self.cache.set_limit(entries, bytes=bytes)

    def cache_info(self) -> CacheInfo:
        return self.cache.info()

    def clear_cache(self) -> None:
        self.cache.clear()

    # --------------------------------------------------- observability

    def metrics(self, *, deterministic_only: bool = False
                ) -> Dict[str, float]:
        """Snapshot of every metric series in this replica's scope:
        its own registry, its engine cache's, and — when attached — the
        sync node's. With `deterministic_only`, just the aggregates
        that are a pure function of the converged contribution set
        (identical across replicas and delivery orders; what the SEC
        telemetry tests compare)."""
        scopes = [self.obs]
        if self.cache.obs is not self.obs:
            scopes.append(self.cache.obs)
        node_obs = getattr(self._node, "obs", None)
        if node_obs is not None and node_obs is not self.obs:
            scopes.append(node_obs)
        if deterministic_only:
            out: Dict[str, float] = {}
            for s in scopes:
                out.update(s.aggregate())
            return out
        return scopes[0].merged(*scopes[1:])

    def trace_to(self, path: str) -> int:
        """Export this replica's telemetry as JSONL: one meta header,
        the process tracer's finished spans (if tracing is on), then
        every metric series from metrics(). Returns lines written."""
        from repro.obs import current_tracer, to_events, write_jsonl
        from repro.obs.trace import NULL_TRACER
        tracer = current_tracer()
        events = to_events(
            tracer=None if tracer is NULL_TRACER else tracer,
            meta={"node": self.node_id})
        for name, value in sorted(self.metrics().items()):
            events.append({"kind": "metric", "name": name,
                           "value": value})
        return write_jsonl(path, events)

    def __repr__(self) -> str:
        where = f" via {self._node.node_id!r}" if self._node else ""
        ev = len(self.trust.evidence) if self.trust is not None else 0
        return (f"Replica({self.node_id!r}{where}, "
                f"visible={len(self.state.visible())}, evidence={ev}, "
                f"cache={self.cache.info().entries})")
