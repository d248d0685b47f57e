"""Multi-chip sharding tests (virtual 8-device CPU mesh, subprocess)."""

from conftest import run_in_cpu_mesh


def test_sharded_merkleize_chunks_matches_host():
    out = run_in_cpu_mesh(
        """
import numpy as np
from ethereum_consensus_tpu.parallel import chip_mesh, sharded_merkleize_chunks
from ethereum_consensus_tpu.ssz.merkle import merkleize_chunks

rng = np.random.default_rng(3)
mesh = chip_mesh(8)
for count, limit in [(8, None), (64, None), (100, 4096), (1024, 2**40)]:
    chunks = rng.integers(0, 256, size=count * 32, dtype=np.uint8).tobytes()
    got = sharded_merkleize_chunks(chunks, mesh, limit=limit)
    want = merkleize_chunks(chunks, limit=limit)
    assert got == want, (count, limit, got.hex(), want.hex())
print("sharded-merkle-ok")
"""
    )
    assert "sharded-merkle-ok" in out


def test_chain_step_dryrun():
    out = run_in_cpu_mesh(
        """
import __graft_entry__ as g
g.dryrun_multichip(8)
"""
    )
    assert "dryrun_multichip ok" in out
    # the widened tail: mesh-sharded set aggregation + a full signed block
    # (attestations + sync aggregate, batched sigs) device==host
    assert "sharded_set_agg" in out
    assert "device==host root" in out


def test_entry_compiles():
    out = run_in_cpu_mesh(
        """
import jax
import __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn)(*args)
assert out.shape == (8,) and str(out.dtype) == "uint32"
print("entry-ok")
"""
    )
    assert "entry-ok" in out


def test_sharded_merkleize_small_and_odd_meshes():
    """Regression: small chunk counts (< mesh size) and non-power-of-two
    meshes must fall back to the host merkleizer instead of crashing."""
    out = run_in_cpu_mesh(
        """
import numpy as np
from ethereum_consensus_tpu.parallel import chip_mesh, sharded_merkleize_chunks
from ethereum_consensus_tpu.ssz.merkle import merkleize_chunks

rng = np.random.default_rng(5)
for n_dev, count, limit in [(8, 4, None), (8, 1, None), (6, 64, None),
                            (8, 3, 4096), (5, 17, 64)]:
    mesh = chip_mesh(n_dev)
    chunks = rng.integers(0, 256, size=count * 32, dtype=np.uint8).tobytes()
    got = sharded_merkleize_chunks(chunks, mesh, limit=limit)
    want = merkleize_chunks(chunks, limit=limit)
    assert got == want, (n_dev, count, limit, got.hex(), want.hex())
print("small-odd-ok")
"""
    )
    assert "small-odd-ok" in out


def test_chain_step_rejects_non_pow2_local_chunks():
    """Regression: a per-device chunk count that is not a power of two would
    silently produce a wrong root; the step must refuse to trace."""
    out = run_in_cpu_mesh(
        """
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from ethereum_consensus_tpu.ops.merkle import zero_hash_words
from ethereum_consensus_tpu.parallel import chip_mesh, make_chain_step
from ethereum_consensus_tpu.parallel.step import _length_words

mesh = chip_mesh(2)
step = make_chain_step(mesh)
n = 24  # 12 per device -> 3 chunks: not a power of two
balances = jnp.asarray(np.full(n, 32 * 10**9, dtype=np.uint64))
eff = jnp.asarray(np.full(n, 32 * 10**9, dtype=np.uint64))
active = jnp.asarray(np.ones(n, dtype=bool))
zw = jnp.asarray(zero_hash_words())
try:
    step(balances, eff, active, zw, jnp.asarray(_length_words(n)))
except ValueError as e:
    assert "power of two" in str(e), e
    print("step-reject-ok")
else:
    raise AssertionError("expected ValueError for non-pow2 local chunks")
""",
        n_devices=2,
    )
    assert "step-reject-ok" in out


def test_run_chain_step_arbitrary_sizes():
    """run_chain_step pads any registry size (incl. primes and counts
    smaller than the mesh) and still matches the host merkleizer + totals."""
    out = run_in_cpu_mesh(
        """
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from ethereum_consensus_tpu.ops.merkle import zero_hash_words
from ethereum_consensus_tpu.parallel import chip_mesh, make_chain_step
from ethereum_consensus_tpu.parallel.step import run_chain_step
from ethereum_consensus_tpu.ssz import List, uint64

mesh = chip_mesh(8)
step = make_chain_step(mesh)
zw = jnp.asarray(zero_hash_words())
rng = np.random.default_rng(11)
typ = List[uint64, 2**40]
for n in (5, 8, 37, 64, 127, 1234):
    balances = rng.integers(1, 40 * 10**9, size=n, dtype=np.uint64)
    eff = np.full(n, 32 * 10**9, dtype=np.uint64)
    active = rng.integers(0, 2, size=n).astype(bool)
    new_eff, total, root = run_chain_step(step, mesh, balances, eff, active, zw)
    want_root = typ.hash_tree_root([int(b) for b in balances])
    got_root = np.asarray(root).astype(">u4").tobytes()
    assert got_root == want_root, (n, got_root.hex(), want_root.hex())
    want_total = sum(int(e) for e, a in zip(new_eff, active) if a)
    assert int(total) == want_total, (n, int(total), want_total)
print("arbitrary-sizes-ok")
"""
    )
    assert "arbitrary-sizes-ok" in out


def test_epoch_sweep_step_matches_host_process_epoch():
    """The distributed epoch sweep (flag deltas + inactivity, psum'd
    totals) must reproduce the host altair epoch functions bit-for-bit on
    a real attested state with a NON-ALIGNED registry, sharded over the
    8-device mesh."""
    out = run_in_cpu_mesh(
        """
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
import sys, os
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
from chain_utils import fresh_genesis_altair, make_attestation, produce_block_altair
from ethereum_consensus_tpu.models.altair.state_transition import state_transition
from ethereum_consensus_tpu.models.altair.slot_processing import process_slots
from ethereum_consensus_tpu.models.altair import helpers as ah
from ethereum_consensus_tpu.models.altair.epoch_processing import (
    process_inactivity_updates, process_rewards_and_penalties,
)
from ethereum_consensus_tpu.models.registry_columns import pack_registry
from ethereum_consensus_tpu.parallel import chip_mesh
from ethereum_consensus_tpu.parallel.step import (
    make_epoch_sweep_step, pad_registry_for_mesh,
)

state, ctx = fresh_genesis_altair(29, "minimal")  # non-aligned registry
# advance past epoch 1 so the epoch stages are NOT the genesis no-op and
# previous-epoch participation is real
while state.slot < 2 * ctx.SLOTS_PER_EPOCH + 1:
    target = state.slot + 1
    atts = [make_attestation(state, state.slot, 0, ctx)] if state.slot + ctx.MIN_ATTESTATION_INCLUSION_DELAY <= target else []
    signed = produce_block_altair(state.copy(), target, ctx, attestations=atts)
    state_transition(state, signed, ctx)

# host reference: the two epoch stages on a copy
host = state.copy()
process_inactivity_updates(host, ctx)
process_rewards_and_penalties(host, ctx)

# device: one sharded sweep over the 8-device mesh
n = len(state.validators)
prev = ah.get_previous_epoch(state, ctx)
cur = ah.get_current_epoch(state, ctx)
is_leaking = ah.is_in_inactivity_leak(state, ctx)
packed = pack_registry(state, prev, use_current_participation=(prev == cur))
active_cur = np.fromiter(
    (v.activation_epoch <= cur < v.exit_epoch for v in state.validators),
    np.bool_, n,
)

mesh = chip_mesh(8)
sweep = make_epoch_sweep_step(mesh, ctx, is_leaking=is_leaking)
padded = pad_registry_for_mesh(n, 8)

def pad(arr, dtype):
    out = np.zeros(padded, dtype)
    out[:n] = arr
    return jnp.asarray(out)

new_balances, new_scores, total_active = jax.block_until_ready(
    sweep(
        pad(packed["balances"], np.uint64),
        pad(packed["effective_balance"], np.uint64),
        pad(packed["previous_participation"], np.uint8),
        pad(packed["slashed"], np.bool_),
        pad(packed["active_previous"], np.bool_),
        pad(active_cur, np.bool_),
        pad(packed["eligible"], np.bool_),
        pad(packed["inactivity_scores"], np.uint64),
    )
)
got_balances = [int(b) for b in np.asarray(new_balances)[:n]]
got_scores = [int(s) for s in np.asarray(new_scores)[:n]]
assert got_balances == [int(b) for b in host.balances], "balances mismatch"
assert got_scores == [int(s) for s in host.inactivity_scores], "scores mismatch"
assert int(total_active) == ah.get_total_active_balance(state, ctx)
print("epoch-sweep-ok")
"""
    )
    assert "epoch-sweep-ok" in out


def test_sharded_signature_set_aggregation_uneven_shapes():
    """The batch-verify set axis sharded over the mesh with UNEVEN shapes
    — a set count not divisible by the mesh and ragged per-set key counts
    (the padded segmented-fold path) — cross-checked key-exact against
    the host aggregator. Complements the aligned-shape case exercised by
    the dryrun (test_chain_step_dryrun); VERDICT r2 item 5."""
    out = run_in_cpu_mesh(
        """
import numpy as np
from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.native import bls as native_bls
from ethereum_consensus_tpu.ops import g1 as device_g1

key_counts = [3, 1, 5, 2, 4, 2, 1, 6, 3, 2, 1, 4, 2]  # 13 sets, ragged
sks, sets = [], []
i = 0
for count in key_counts:
    group = [bls.SecretKey(700 + i + j) for j in range(count)]
    i += count
    sks.append(group)
    sets.append([sk.public_key().raw_uncompressed() for sk in group])
agg = device_g1.aggregate_pubkey_sets_device(sets)
for s, (raw, inf) in enumerate(agg):
    want = bls.eth_aggregate_public_keys([sk.public_key() for sk in sks[s]])
    assert not inf and native_bls.g1_compress_raw(raw) == want.to_bytes(), s
print("sharded-set-agg-ok")
"""
    )
    assert "sharded-set-agg-ok" in out


def test_sharded_batch_pairing_matches_host_verdicts():
    """The mesh-sharded RLC batch pairing (parallel/pairing.py): an
    UNEVEN set count (11 over 8 devices — one ragged lane per shard plus
    padding) must accept a valid batch and reject a tampered one, and
    `verify_signature_sets` with the pairing flag installed must route
    through the sharded path to the same verdicts as the host batch;
    VERDICT r2 item 5 (shard the signature batch over the mesh)."""
    out = run_in_cpu_mesh(
        """
import jax
jax.config.update("jax_enable_x64", True)
from ethereum_consensus_tpu import ops
from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.native import bls as native_bls
from ethereum_consensus_tpu.parallel.mesh import chip_mesh
from ethereum_consensus_tpu.parallel.pairing import batch_verify_sharded

n = 11
sks = [bls.SecretKey(i + 101) for i in range(n)]
pk_raws, h_raws, sig_raws, scalars, sets = [], [], [], [], []
for i, sk in enumerate(sks):
    msg = b"m" * 31 + bytes([i])
    sig = sk.sign(msg)
    pk_raws.append(sk.public_key().raw_uncompressed())
    rc, raw, _ = native_bls.g2_decompress(
        native_bls.hash_to_g2_compressed(msg, bls.ETH_DST),
        check_subgroup=False,
    )
    assert rc == 0
    h_raws.append(raw)
    sig_raws.append(sig.raw_uncompressed())
    scalars.append(i * 7 + 3)
    sets.append(bls.SignatureSet([sk.public_key()], msg, sig))

mesh = chip_mesh()
assert mesh.devices.size == 8
assert batch_verify_sharded(pk_raws, h_raws, sig_raws, scalars, mesh=mesh)
bad_sigs = list(sig_raws)
bad_sigs[5] = sig_raws[6]
assert not batch_verify_sharded(pk_raws, h_raws, bad_sigs, scalars, mesh=mesh)

# end-to-end routing: verify_signature_sets -> sharded pairing
ops.install(pairing_min_sets=1)
try:
    assert bls.verify_signature_sets(sets) == [True] * n
    forged = list(sets)
    forged[4] = bls.SignatureSet(
        [sks[4].public_key()], b"f" * 32, sets[4].signature
    )
    assert bls.verify_signature_sets(forged) == [True] * 4 + [False] + [True] * 6
finally:
    ops.uninstall()
print("sharded-pairing-ok")
"""
    )
    assert "sharded-pairing-ok" in out


def test_device_pairing_multikey_sets_use_segmented_fold():
    """Multi-key signature sets through the device pairing route must
    pre-aggregate with the ONE segmented device fold (ops/g1.py), not a
    serial host add loop — and the verdicts must match the host batch
    exactly (valid batch, tampered batch, identity-aggregate batch).
    Routing check: the host add is monkeypatched to count calls; the
    device route must never call it. VERDICT r3 item 4."""
    out = run_in_cpu_mesh(
        """
import jax
jax.config.update("jax_enable_x64", True)
from ethereum_consensus_tpu import ops
from ethereum_consensus_tpu.crypto import bls
from ethereum_consensus_tpu.native import bls as native_bls

key_counts = [3, 1, 5, 2, 4]  # ragged multi-key sets (atts + sync shape)
groups, sets = [], []
i = 0
for count in key_counts:
    group = [bls.SecretKey(8800 + i + j) for j in range(count)]
    i += count
    msg = b"k" * 31 + bytes([count])
    agg = bls.aggregate([sk.sign(msg) for sk in group])
    groups.append(group)
    sets.append(bls.SignatureSet([sk.public_key() for sk in group], msg, agg))

calls = {"n": 0}
real_add = native_bls.g1_add_raw
def counting_add(*a, **k):
    calls["n"] += 1
    return real_add(*a, **k)
native_bls.g1_add_raw = counting_add
# pairing on, device set-agg threshold OFF: _batch_device_pairing itself
# must own the multi-key aggregation via the segmented fold
ops.install(pairing_min_sets=1, bls_agg_min_n=1 << 60)
try:
    assert bls.verify_signature_sets(sets) == [True] * len(sets)
    assert calls["n"] == 0, f"host add loop ran {calls['n']} times"
    forged = list(sets)
    forged[2] = bls.SignatureSet(
        sets[2].public_keys, b"x" * 32, sets[2].signature
    )
    verdicts = bls.verify_signature_sets(forged)
    assert verdicts == [True, True, False, True, True], verdicts
finally:
    ops.uninstall()
    native_bls.g1_add_raw = real_add
print("segmented-fold-pairing-ok")
"""
    )
    assert "segmented-fold-pairing-ok" in out
