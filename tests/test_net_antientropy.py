"""Merkle-partitioned anti-entropy: correctness of the digest-driven
reconciliation protocol over real serialized frames.

Sessions must (a) produce identical Merkle roots, item sets, and stores;
(b) propagate tombstones, not just visible elements; (c) ship nothing
when replicas already agree; and (d) ship far fewer bytes than full-state
push when the difference is small.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.delta import apply_delta, delta_for_entries
from repro.core.gossip import GossipNetwork
from repro.core.merkle import (
    bucket_digests, diff_buckets, merkle_levels, pick_bucket_bits,
    prefix_bucket, subtree_digest)
from repro.core.state import CRDTMergeState
from repro.core.version_vector import VersionVector
from repro.net.antientropy import reconcile_root, SyncNode
from repro.net.transport import (
    InMemoryTransport, LoopbackSocketTransport, pump)
from repro.net.wire import frame_size, state_to_msg


def _payload(rng, shape=(4, 4)):
    return {"w": jnp.asarray(rng.standard_normal(shape), jnp.float32)}


def _sync(a: SyncNode, b: SyncNode, transport=None) -> InMemoryTransport:
    t = transport or InMemoryTransport()
    t.register(a.node_id)
    t.register(b.node_id)
    t.send(a.node_id, b.node_id, a.begin_sync(b.node_id))
    pump({a.node_id: a, b.node_id: b}, t)
    return t


def _assert_converged(a: SyncNode, b: SyncNode, stores: bool = True):
    assert a.root() == b.root()
    assert reconcile_root(a.state) == reconcile_root(b.state)
    assert a.state.adds == b.state.adds
    assert a.state.removes == b.state.removes
    if stores:
        assert set(a.state.store) == set(b.state.store)


# ----------------------------------------------------------- merkle bits


def test_bucket_digests_localise_difference():
    rng = np.random.default_rng(0)
    leaves = [bytes(rng.bytes(32)) for _ in range(64)]
    bits = pick_bucket_bits(len(leaves))
    d1 = bucket_digests(leaves, bits)
    d2 = bucket_digests(leaves + [b"\xff" * 32], bits)
    diff = diff_buckets(d1, d2)
    assert diff == [prefix_bucket(b"\xff" * 32, bits)]
    assert diff_buckets(d1, bucket_digests(list(leaves), bits)) == []


def test_bucket_digest_order_independent():
    rng = np.random.default_rng(1)
    leaves = [bytes(rng.bytes(32)) for _ in range(40)]
    shuffled = list(leaves)
    rng.shuffle(shuffled)
    assert bucket_digests(leaves, 4) == bucket_digests(shuffled, 4)


def test_subtree_digest_accessor():
    leaves = sorted(bytes([i]) * 32 for i in range(8))
    levels = merkle_levels(leaves)
    assert subtree_digest(levels, 0, 0) == leaves[0]
    assert subtree_digest(levels, len(levels) - 1, 0) == levels[-1][0]
    with pytest.raises(IndexError):
        subtree_digest(levels, 0, 99)


def test_pick_bucket_bits_scales():
    assert pick_bucket_bits(0) == 0
    assert pick_bucket_bits(4) == 0
    assert pick_bucket_bits(1000) > pick_bucket_bits(50) > 0
    assert pick_bucket_bits(10 ** 9) <= 10


# ----------------------------------------------------- delta_for_entries


def test_delta_for_entries_equals_merge():
    rng = np.random.default_rng(2)
    s1, s2 = CRDTMergeState(), CRDTMergeState()
    for i in range(3):
        s1 = s1.add(_payload(rng), node="a")
        s2 = s2.add(_payload(rng), node="b")
    s2 = s2.remove(sorted(s2.visible())[0], "b")
    d = delta_for_entries(s2, s2.adds, s2.removes, include_payloads=True)
    assert apply_delta(s1, d) == s1.merge(s2)


# ------------------------------------------------------------- two-node


def test_two_node_sync_bidirectional():
    rng = np.random.default_rng(3)
    a, b = SyncNode("a"), SyncNode("b")
    for _ in range(4):
        a.contribute(_payload(rng))
    for _ in range(3):
        b.contribute(_payload(rng))
    _sync(a, b)
    _assert_converged(a, b)


def test_sync_propagates_tombstones():
    rng = np.random.default_rng(4)
    a, b = SyncNode("a"), SyncNode("b")
    shared = _payload(rng)
    a.contribute(shared)
    b.contribute(shared)          # same content => same element, two tags
    _sync(a, b)
    victim = sorted(a.state.visible())[0]
    a.retract(victim)
    assert victim in b.state.visible()
    _sync(a, b)
    _assert_converged(a, b)
    assert victim not in b.state.visible()


def test_in_sync_replicas_exchange_only_digests():
    rng = np.random.default_rng(5)
    a, b = SyncNode("a"), SyncNode("b")
    for _ in range(5):
        p = _payload(rng, (16, 16))
        a.contribute(p)
    _sync(a, b)                                   # actual transfer
    t2 = _sync(a, b)                              # replicas now identical
    # second session: SyncReq + SyncDone only, no items, no blobs
    assert set(t2.bytes_by_type) == {"SyncReq", "SyncDone"}
    full = frame_size(state_to_msg(a.state, "a"))
    assert t2.bytes_sent < full / 10


def test_small_difference_ships_small_bytes():
    rng = np.random.default_rng(6)
    a, b = SyncNode("a"), SyncNode("b")
    for _ in range(20):
        p = _payload(rng, (32, 32))
        a.contribute(p)
    _sync(a, b)
    a.contribute(_payload(rng, (32, 32)))         # one new element
    t = _sync(a, b)
    full = frame_size(state_to_msg(a.state, "a"))
    assert t.bytes_sent < full / 3
    _assert_converged(a, b)


def test_blob_recovery_for_entry_without_payload():
    """A replica holding an add entry but no blob fetches it on sync."""
    rng = np.random.default_rng(7)
    a, b = SyncNode("a"), SyncNode("b")
    a.contribute(_payload(rng))
    # b learns the metadata only (payload-less delta)
    d = delta_for_entries(a.state, a.state.adds, a.state.removes)
    b.state = apply_delta(b.state, d)
    assert b.missing_blobs()
    _sync(b, a)                                   # b initiates
    assert not b.missing_blobs()
    _assert_converged(a, b)


def test_compressed_blob_sync_deterministic():
    rng = np.random.default_rng(8)
    a = SyncNode("a", compress_blobs=True)
    b = SyncNode("b", compress_blobs=True)
    a.contribute(_payload(rng, (16, 16)))
    _sync(a, b)
    assert a.root() == b.root()
    eid = next(iter(a.state.visible()))
    # quantized transfer: b's copy equals dequantize(quantize(a's copy))
    from repro.core.compression import compress_tree, decompress_tree
    expect = decompress_tree(compress_tree(a.state.store[eid]))
    got = b.state.store[eid]
    assert np.asarray(expect["w"]).tobytes() == np.asarray(got["w"]).tobytes()


def test_keep_quantized_stores_int8_payloads():
    """keep_quantized=True stores arriving CompressedTree payloads
    as-is (the engine densifies each int8 slice where it reads it)
    instead of densifying on arrival; content identity is unchanged because
    digests are defined on dequantized values."""
    from repro.core.compression import CompressedTree, decompress_tree
    rng = np.random.default_rng(21)
    a = SyncNode("a", compress_blobs=True)
    b = SyncNode("b", compress_blobs=True, keep_quantized=True)
    a.contribute(_payload(rng, (16, 16)))
    _sync(a, b)
    assert a.root() == b.root()
    eid = next(iter(a.state.visible()))
    got = b.state.store[eid]
    assert isinstance(got, CompressedTree)
    # dequantizing b's stored wire payload reproduces exactly what a
    # default receiver would have stored
    from repro.core.compression import compress_tree
    expect = decompress_tree(compress_tree(a.state.store[eid]))
    dense = decompress_tree(got)
    assert np.asarray(expect["w"]).tobytes() == \
        np.asarray(dense["w"]).tobytes()


def test_keep_quantized_large_blob_chunk_path():
    """The chunked blob-stream reassembly path (_finish_blob) honours
    keep_quantized too: a blob too big for one frame still lands in the
    store as a CompressedTree."""
    from repro.core.compression import CompressedTree
    rng = np.random.default_rng(22)
    a = SyncNode("a", compress_blobs=True, max_frame_bytes=2048)
    b = SyncNode("b", compress_blobs=True, keep_quantized=True,
                 max_frame_bytes=2048)
    a.contribute(_payload(rng, (64, 64)))      # 16 KiB dense > frame
    _sync(a, b)
    _sync(b, a)
    assert a.root() == b.root()
    eid = next(iter(a.state.visible()))
    assert eid in b.state.store
    assert isinstance(b.state.store[eid], CompressedTree)


# ------------------------------------------------------------ multi-node


def test_mesh_of_nodes_converges_via_pairwise_sessions():
    rng = np.random.default_rng(9)
    nodes = {f"n{i}": SyncNode(f"n{i}") for i in range(6)}
    for node in nodes.values():
        node.contribute(_payload(rng))
    t = InMemoryTransport()
    for nid in nodes:
        t.register(nid)
    ids = sorted(nodes)
    for r in range(3):                 # ring sessions: n0->n1->...->n0
        for i, nid in enumerate(ids):
            peer = ids[(i + 1) % len(ids)]
            t.send(nid, peer, nodes[nid].begin_sync(peer))
            pump(nodes, t)
    roots = {n.root() for n in nodes.values()}
    assert len(roots) == 1
    assert all(not n.missing_blobs() for n in nodes.values())


def test_invalid_bits_dropped_not_crashed():
    """A well-framed SyncReq with out-of-range bucket bits (wire allows a
    full u8) is dropped as a protocol error, not raised out of handle()."""
    from repro.net.wire import SyncReq
    b = SyncNode("b")
    b.contribute(_payload(np.random.default_rng(20)))
    before = b.state
    replies = b.handle(SyncReq("a", 1, b"\x00" * 32, 20, VersionVector()))
    assert replies == []
    assert b.state is before
    assert b.stats["protocol_error_bits"] == 1


def test_resolve_cache_distinguishes_cfg_and_base():
    """Same state, different strategy knobs/base => different outputs,
    never a stale aliased cache entry."""
    from repro.api import MergeSpec
    from repro.core.resolve import clear_cache, resolve
    rng = np.random.default_rng(21)
    s = CRDTMergeState()
    for _ in range(3):
        s = s.add(_payload(rng)["w"], node="a")
    clear_cache()
    lo, hi = MergeSpec("slerp", {"t": 0.1}), MergeSpec("slerp", {"t": 0.9})
    r_lo = resolve(s, lo)
    r_hi = resolve(s, hi)
    assert not bool(jnp.array_equal(r_lo, r_hi))
    assert resolve(s, lo) is r_lo      # both stay cached
    assert resolve(s, hi) is r_hi
    clear_cache()


def test_interop_with_plain_state_push():
    """SyncNode accepts legacy full-state pushes too."""
    rng = np.random.default_rng(10)
    a, b = SyncNode("a"), SyncNode("b")
    a.contribute(_payload(rng))
    b.handle(state_to_msg(a.state, "a"))
    assert a.root() == b.root()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_sync_property_random_divergence(seed):
    rng = np.random.default_rng(seed)
    a, b = SyncNode("a"), SyncNode("b")
    for _ in range(int(rng.integers(1, 5))):
        a.contribute(_payload(rng, (2, 2)))
    for _ in range(int(rng.integers(0, 4))):
        b.contribute(_payload(rng, (2, 2)))
    if rng.random() < 0.5 and a.state.visible():
        a.retract(sorted(a.state.visible())[0])
    _sync(a, b)
    _assert_converged(a, b)


# --------------------------------------------------- gossip over transports


@pytest.mark.parametrize("use_deltas", [False, True])
def test_gossip_network_over_wire_matches_legacy(use_deltas):
    rng = np.random.default_rng(11)
    payloads = [_payload(rng) for _ in range(6)]

    legacy = GossipNetwork(6, seed=1, use_deltas=use_deltas)
    wired = GossipNetwork(6, seed=1, use_deltas=use_deltas,
                          transport=InMemoryTransport())
    for net in (legacy, wired):
        for i, node in enumerate(net.nodes):
            node.contribute(payloads[i])
    order = [(i, j) for i in range(6) for j in range(6) if i != j]
    legacy.all_pairs_round(order=order)
    wired.all_pairs_round(order=order)
    assert legacy.converged() and wired.converged()
    assert legacy.roots()[0] == wired.roots()[0]
    assert wired.bytes_sent > 0


def test_gossip_network_over_loopback_sockets():
    rng = np.random.default_rng(12)
    t = LoopbackSocketTransport()
    try:
        net = GossipNetwork(4, seed=2, transport=t)
    except OSError:
        pytest.skip("loopback sockets unavailable in this sandbox")
    try:
        for node in net.nodes:
            node.contribute(_payload(rng))
        for _ in range(2):
            net.all_pairs_round()
        assert net.converged()
    finally:
        t.close()


def test_sync_over_loopback_sockets():
    rng = np.random.default_rng(13)
    t = LoopbackSocketTransport()
    try:
        a, b = SyncNode("a"), SyncNode("b")
        a.contribute(_payload(rng))
        b.contribute(_payload(rng))
        _sync(a, b, transport=t)
    except OSError:
        pytest.skip("loopback sockets unavailable in this sandbox")
    finally:
        t.close()
    _assert_converged(a, b)
