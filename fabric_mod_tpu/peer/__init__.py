"""L5 peer: block validation (one device batch per block), committer,
channel wiring, endorsement, chaincode runtime, deliver client, MCS."""
from fabric_mod_tpu.peer.txvalidator import (  # noqa: F401
    Committer, TxValidator, ValidationInfoProvider)
from fabric_mod_tpu.peer.channel import Channel          # noqa: F401
from fabric_mod_tpu.peer.commitpipe import (             # noqa: F401
    PipelinedCommitter, ValidatorCommitTarget)
from fabric_mod_tpu.peer.chaincode import (              # noqa: F401
    ChaincodeRegistry, ChaincodeStub, KvContract)
from fabric_mod_tpu.peer.deliverclient import DeliverClient  # noqa: F401
from fabric_mod_tpu.peer.endorser import Endorser        # noqa: F401
from fabric_mod_tpu.peer.lifecycle import (              # noqa: F401
    LifecycleContract, LifecycleValidationInfo)
from fabric_mod_tpu.peer.mcs import MessageCryptoService  # noqa: F401
