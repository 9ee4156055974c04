"""Electra fork tests: deneb→electra boundary, EIP-7549 attestations through
the full chain, EIP-7251 consolidations/maxEB, EIP-7002 withdrawal requests,
EIP-6110 pending deposits (VERDICT r1 item 7)."""

import pytest

from lighthouse_tpu.chain import BeaconChainHarness
from lighthouse_tpu.consensus import electra as el
from lighthouse_tpu.consensus import helpers as h
from lighthouse_tpu.crypto.bls.backends import set_backend
from lighthouse_tpu.types.spec import FAR_FUTURE_EPOCH, minimal_spec


@pytest.fixture(autouse=True)
def _fake_backend():
    set_backend("fake")
    yield
    set_backend("host")


def electra_harness(**spec_overrides):
    spec = minimal_spec(
        altair_fork_epoch=0, bellatrix_fork_epoch=0, capella_fork_epoch=0,
        deneb_fork_epoch=0, electra_fork_epoch=0, **spec_overrides,
    )
    return BeaconChainHarness(validator_count=16, spec=spec, fake_crypto=True)


def test_genesis_on_electra_and_finalization():
    """A chain born on electra finalizes under EIP-7549 attestations."""
    harness = electra_harness()
    state = harness.chain.head_state
    assert type(state).fork_name == "electra"
    assert int(state.deposit_requests_start_index) > 0  # UNSET sentinel
    harness.extend_chain(harness.spec.slots_per_epoch * 5)
    assert harness.finalized_epoch() >= 2, "electra chain must finalize"


def test_deneb_to_electra_boundary():
    """Cross the fork mid-chain: state upgrades, blocks switch container,
    finalization continues."""
    spec = minimal_spec(
        altair_fork_epoch=0, bellatrix_fork_epoch=0, capella_fork_epoch=0,
        deneb_fork_epoch=0, electra_fork_epoch=2,
    )
    harness = BeaconChainHarness(validator_count=16, spec=spec, fake_crypto=True)
    harness.extend_chain(spec.slots_per_epoch)  # epoch 0->1, still deneb
    assert type(harness.head_state).fork_name == "deneb"
    harness.extend_chain(spec.slots_per_epoch * 3)
    assert type(harness.head_state).fork_name == "electra"
    st = harness.head_state
    assert hasattr(st, "pending_deposits")
    assert int(st.earliest_exit_epoch) >= 2
    harness.extend_chain(spec.slots_per_epoch * 2)
    assert harness.finalized_epoch() >= 2, "finalization must survive the fork"


def test_electra_attestation_indexing():
    """get_indexed_attestation resolves committee_bits spans correctly."""
    harness = electra_harness()
    harness.extend_chain(2)
    chain = harness.chain
    slot = chain.current_slot()
    state, _ = chain.state_at_slot(slot)
    spec = harness.spec
    committees = h.get_committee_count_per_slot(
        state, h.compute_epoch_at_slot(slot, spec), spec
    )
    data = chain.produce_attestation_data(slot, 0)
    assert int(data.index) == 0
    # attestation spanning ALL committees of the slot
    bits = []
    expected = []
    for ci in range(committees):
        committee = h.get_beacon_committee(state, slot, ci, spec)
        bits.extend([True] * len(committee))
        expected.extend(int(v) for v in committee)
    committee_bits = [i < committees for i in range(spec.preset.max_committees_per_slot)]
    att = harness.types.AttestationElectra(
        aggregation_bits=bits, data=data, signature=b"\xc0" + b"\x00" * 95,
        committee_bits=committee_bits,
    )
    indexed = h.get_indexed_attestation(state, att, harness.types, spec)
    assert list(indexed.attesting_indices) == sorted(set(expected))


def test_withdrawal_request_full_exit():
    harness = electra_harness(shard_committee_period=0)
    harness.extend_chain(1)
    chain = harness.chain
    state = chain.head_state.copy()
    v = state.validators[3]
    # give it execution (0x01) credentials so the EL can direct an exit
    addr = bytes(range(20))
    v.withdrawal_credentials = b"\x01" + b"\x00" * 11 + addr
    req = harness.types.WithdrawalRequest(
        source_address=addr, validator_pubkey=bytes(v.pubkey),
        amount=harness.spec.full_exit_request_amount,
    )
    assert v.exit_epoch == FAR_FUTURE_EPOCH
    el.process_withdrawal_request(state, req, harness.types, harness.spec)
    assert state.validators[3].exit_epoch != FAR_FUTURE_EPOCH

    # wrong source address is silently dropped
    state2 = chain.head_state.copy()
    v2 = state2.validators[4]
    v2.withdrawal_credentials = b"\x01" + b"\x00" * 11 + addr
    bad = harness.types.WithdrawalRequest(
        source_address=b"\xff" * 20, validator_pubkey=bytes(v2.pubkey), amount=0
    )
    el.process_withdrawal_request(state2, bad, harness.types, harness.spec)
    assert state2.validators[4].exit_epoch == FAR_FUTURE_EPOCH


def test_withdrawal_request_partial():
    harness = electra_harness(shard_committee_period=0)
    harness.extend_chain(1)
    state = harness.chain.head_state.copy()
    spec = harness.spec
    addr = bytes(range(20))
    v = state.validators[5]
    v.withdrawal_credentials = spec.compounding_withdrawal_prefix + b"\x00" * 11 + addr
    state.balances[5] = spec.min_activation_balance + 7 * 10**9
    req = harness.types.WithdrawalRequest(
        source_address=addr, validator_pubkey=bytes(v.pubkey), amount=5 * 10**9
    )
    el.process_withdrawal_request(state, req, harness.types, spec)
    assert len(state.pending_partial_withdrawals) == 1
    w = state.pending_partial_withdrawals[0]
    assert int(w.validator_index) == 5 and int(w.amount) == 5 * 10**9
    # validator keeps FAR_FUTURE exit (partial, not full)
    assert state.validators[5].exit_epoch == FAR_FUTURE_EPOCH


def test_consolidation_switch_to_compounding():
    harness = electra_harness(shard_committee_period=0)
    harness.extend_chain(1)
    state = harness.chain.head_state.copy()
    spec = harness.spec
    addr = bytes(range(20))
    v = state.validators[6]
    v.withdrawal_credentials = b"\x01" + b"\x00" * 11 + addr
    state.balances[6] = spec.min_activation_balance + 3 * 10**9
    req = harness.types.ConsolidationRequest(
        source_address=addr,
        source_pubkey=bytes(v.pubkey),
        target_pubkey=bytes(v.pubkey),  # self => switch to compounding
    )
    el.process_consolidation_request(state, req, harness.types, spec)
    assert h.has_compounding_withdrawal_credential(state.validators[6], spec)
    # excess above 32 ETH banked as a pending deposit
    assert int(state.balances[6]) == spec.min_activation_balance
    assert len(state.pending_deposits) == 1
    assert int(state.pending_deposits[0].amount) == 3 * 10**9


def test_pending_deposit_flow():
    """A deposit request parks in the queue and is applied at the epoch
    boundary once its slot is finalized."""
    harness = electra_harness()
    harness.extend_chain(1)
    state = harness.chain.head_state.copy()
    spec, types = harness.spec, harness.types
    # top-up for an EXISTING validator skips signature checks entirely
    pk0 = bytes(state.validators[0].pubkey)
    req = types.DepositRequest(
        pubkey=pk0, withdrawal_credentials=bytes(state.validators[0].withdrawal_credentials),
        amount=10**9, signature=b"\x00" * 96, index=0,
    )
    el.process_deposit_request(state, req, types, spec)
    assert int(state.deposit_requests_start_index) == 0  # first request pins it
    assert len(state.pending_deposits) == 1

    bal_before = int(state.balances[0])
    # eth1 bridge drained + deposit's slot finalized -> processed this epoch
    state.eth1_deposit_index = state.deposit_requests_start_index
    state.finalized_checkpoint = types.Checkpoint(
        epoch=h.get_current_epoch(state, spec) + 1,  # deposit's slot finalized
        root=b"\x00" * 32,
    )
    el.process_pending_deposits(state, types, spec)
    assert len(state.pending_deposits) == 0
    assert int(state.balances[0]) == bal_before + 10**9


def test_effective_balance_cap_compounding():
    """Compounding validators' effective balance rises past 32 ETH at the
    epoch update; eth1-credential validators stay capped."""
    harness = electra_harness()
    harness.extend_chain(1)
    state = harness.chain.head_state.copy()
    spec, types = harness.spec, harness.types
    state.validators[0].withdrawal_credentials = (
        spec.compounding_withdrawal_prefix + bytes(state.validators[0].withdrawal_credentials)[1:]
    )
    state.balances[0] = 100 * 10**9
    state.balances[1] = 100 * 10**9  # bls-credential validator
    from lighthouse_tpu.consensus.per_epoch import (
        EpochArrays,
        _process_effective_balance_updates,
    )

    _process_effective_balance_updates(state, EpochArrays(state, spec), spec)
    assert int(state.validators[0].effective_balance) == 100 * 10**9
    assert int(state.validators[1].effective_balance) == spec.min_activation_balance


def test_exit_churn_is_balance_weighted():
    """A 2048-ETH exit consumes many epochs of churn (EIP-7251)."""
    harness = electra_harness(shard_committee_period=0)
    harness.extend_chain(1)
    state = harness.chain.head_state.copy()
    spec = harness.spec
    state.validators[2].effective_balance = 2048 * 10**9
    h.initiate_validator_exit(state, 2, spec)
    whale_exit = int(state.validators[2].exit_epoch)
    state.validators[3].effective_balance = 32 * 10**9
    h.initiate_validator_exit(state, 3, spec)
    assert int(state.validators[3].exit_epoch) >= whale_exit
    assert whale_exit > h.compute_activation_exit_epoch(
        h.get_current_epoch(state, spec), spec
    ), "a whale exit must push past the base activation-exit epoch"


def test_electra_aggregate_over_committees_on_the_jax_backend(monkeypatch):
    """An EIP-7549 on-chain aggregate over every committee of a slot, built
    through ``consensus/signature_sets`` with real keys, verified by the jax
    backend with its key axis spanning rows (top key bucket shrunk to 4, so
    the slot's 2 committees of 4 keys take two rows) — and refused once a
    committee's signer is missing from the aggregate signature."""
    from lighthouse_tpu import device_telemetry
    from lighthouse_tpu.consensus import signature_sets as ss
    from lighthouse_tpu.crypto.bls import api as bls
    from lighthouse_tpu.crypto.bls.backends import host
    from lighthouse_tpu.crypto.bls.params import R
    from lighthouse_tpu.ops import verify as v
    from lighthouse_tpu.types.spec import DOMAIN_BEACON_ATTESTER

    spec = minimal_spec(
        altair_fork_epoch=0, bellatrix_fork_epoch=0, capella_fork_epoch=0,
        deneb_fork_epoch=0, electra_fork_epoch=0,
    )
    harness = BeaconChainHarness(validator_count=64, spec=spec)
    state = harness.chain.head_state
    slot = int(state.slot)
    epoch = h.compute_epoch_at_slot(slot, spec)
    committees = h.get_committee_count_per_slot(state, epoch, spec)
    assert committees == 2
    data = harness.chain.produce_attestation_data(slot, 0)
    bits, members = [], []
    for ci in range(committees):
        committee = h.get_beacon_committee(state, slot, ci, spec)
        bits += [True] * len(committee)
        members += [int(i) for i in committee]
    root = h.compute_signing_root(
        data.hash_tree_root(),
        h.get_domain(state, DOMAIN_BEACON_ATTESTER, epoch, spec))

    def aggregate(signers):
        sk = bls.SecretKey(sum(harness.keys[i].scalar for i in signers) % R)
        att = harness.types.AttestationElectra(
            aggregation_bits=bits, data=data, signature=sk.sign(root).to_bytes(),
            committee_bits=[ci < committees for ci in
                            range(spec.preset.max_committees_per_slot)])
        indexed = h.get_indexed_attestation(state, att, harness.types, spec)
        return ss.indexed_attestation_signature_set(state, indexed, spec)

    monkeypatch.setattr(v, "K_BUCKETS", (1, 2, 4))
    set_backend("jax")
    for signers, want in ((members, True), (members[1:], False)):
        s = aggregate(signers)
        assert len(s.signing_keys) == 8
        assert host.verify_signature_sets([s], seed=b"el") is want
        assert bls.verify_signature_sets([s]) is want
        rec = device_telemetry.FLIGHT_RECORDER.recent(op="bls_verify")[0]
        assert rec["shape"] == "1x4x2x2" and not rec["host_fallback"]
