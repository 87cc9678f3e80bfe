"""Message crypto service: block signature verification for the peer.

(reference: internal/peer/gossip/mcs.go:124 `VerifyBlock` — data-hash
recomputation + orderer block-signature policy — consumed by the
deliver client at internal/pkg/peer/blocksprovider/blocksprovider.go:227
before a block may enter the commit queue.)

Two halves.  `check_block` is the host half: header and data present,
previous-hash, data-hash, and the SIGNATURES metadata parsed into the
`SignedData` the policy is evaluated over.  The signature half is the
channel's /Channel/Orderer/BlockValidation policy over that list.

`verify_block` runs both on the spot, with one verify call of its own
(gossip, the relay, the orderer's onboarding and follower: none on a
timed path).  The deliver client runs only the host half and submits
the block together with its `SignedData`: the block signature then
rides the block's OWN verify batch (`TxValidator.stage`, after the
transactions' items) and its verdict is the first thing the commit
side reads (`TxValidator.finish`), so a block costs one device call
and not two.  Either way no block whose signature set fails the policy
is committed, flagged or applied.
"""
from __future__ import annotations

from typing import Callable, List, Optional

from fabric_mod_tpu.channelconfig.bundle import Bundle
from fabric_mod_tpu.orderer.blockwriter import block_signed_data
from fabric_mod_tpu.policy.manager import CHANNEL_ORDERER_BLOCK_VALIDATION
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from fabric_mod_tpu.protos.protoutil import SignedData


class BlockVerificationError(Exception):
    """A block failed the MCS gate.  Whatever refuses a block inside a
    commit pipeline (the signature verdict, a config without the
    policy) sets `number`: the deliver client records it and reports
    it to a failover source."""

    def __init__(self, msg: str, number: Optional[int] = None):
        super().__init__(msg)
        self.number = number


def signatures_unsatisfied(number: int) -> BlockVerificationError:
    return BlockVerificationError(
        f"block {number}: signature set does not satisfy "
        f"BlockValidation policy", number)


def block_validation_policy(bundle: Bundle,
                            number: Optional[int] = None):
    """The orderer's BlockValidation policy of `bundle`, for block
    `number`; a config without one fails closed."""
    pol = bundle.policy(CHANNEL_ORDERER_BLOCK_VALIDATION)
    if pol is None:
        raise BlockVerificationError(
            "no orderer BlockValidation policy in channel config",
            number)
    return pol


class MessageCryptoService:
    """`bundle_fn` returns the channel's CURRENT bundle; `verifier` is
    the batch verify seam (TpuVerifier / FakeBatchVerifier)."""

    def __init__(self, bundle_fn: Callable[[], Bundle], verifier=None):
        self._bundle = bundle_fn
        self._verifier = verifier

    def check_block(self, block: m.Block,
                    expected_prev_hash: Optional[bytes] = None
                    ) -> List[SignedData]:
        """The host half: raises BlockVerificationError unless the
        block is well-formed and hash-consistent and carries usable
        signature metadata; returns the block signatures' SignedData,
        which the BlockValidation policy has yet to be held against."""
        if block.header is None or block.data is None:
            raise BlockVerificationError("block missing header/data")
        if expected_prev_hash is not None and \
                block.header.previous_hash != expected_prev_hash:
            raise BlockVerificationError(
                f"block {block.header.number}: previous-hash mismatch")
        if protoutil.block_data_hash(block.data) != block.header.data_hash:
            raise BlockVerificationError(
                f"block {block.header.number}: data hash mismatch")

        md = block.metadata.metadata if block.metadata else []
        idx = m.BlockMetadataIndex.SIGNATURES
        if len(md) <= idx or not md[idx]:
            raise BlockVerificationError(
                f"block {block.header.number}: no signature metadata")
        try:
            meta = m.Metadata.decode(md[idx])
        except Exception as e:
            raise BlockVerificationError(f"bad signature metadata: {e}")
        sds = []
        for sig in meta.signatures:
            try:
                sh = m.SignatureHeader.decode(sig.signature_header)
            except Exception:
                continue
            sds.append(SignedData(
                data=block_signed_data(block, meta.value,
                                       sig.signature_header),
                identity=sh.creator, signature=sig.signature))
        if not sds:
            raise BlockVerificationError(
                f"block {block.header.number}: no usable signatures")
        return sds

    def verify_block(self, channel_id: str, block: m.Block,
                     expected_prev_hash: Optional[bytes] = None) -> None:
        """Raises BlockVerificationError unless the block is
        well-formed, hash-consistent, and signed per the orderer
        block-validation policy (reference: mcs.go:124).  Synchronous:
        one verify call for this block's signatures alone."""
        sds = self.check_block(block, expected_prev_hash)
        pol = block_validation_policy(self._bundle(), block.header.number)
        verify_many = (self._verifier.verify_many
                       if self._verifier is not None else None)
        if not pol.evaluate_signed_data(sds, verify_many):
            raise signatures_unsatisfied(block.header.number)
