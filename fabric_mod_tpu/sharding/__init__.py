"""Channel-sharded scale-out: horizontal placement of channels on
device-mesh slices behind one shared cross-channel verify service.

PAPER.md's L3 makes the channel the natural shard unit — one ledger,
one policy universe, one commit stream per channel — and every PR up
to 12 made ONE channel's commit path faster.  This package is the
layer that turns K chips x N channels into aggregate throughput:

* :mod:`shardmap` — deterministic channel -> mesh-slice placement
  with least-loaded assignment and bounded rebalance on join/leave;
* :mod:`router` — :class:`ChannelShardRouter`, which pins each
  channel's :class:`~fabric_mod_tpu.peer.commitpipe.PipelinedCommitter`
  (via the slice verifier its validator stages against) to its slice;
* :mod:`verifyservice` — :class:`CrossChannelVerifyService`, the
  generalization of :class:`~fabric_mod_tpu.bccsp.tpu.
  BatchingVerifyService` from one program to a service: ONE flusher
  coalescing VerifyItems from every channel, split at flush time into
  per-slice fused dispatches, tagged futures routing verdicts back
  per channel — small channels ride big channels' batches instead of
  each paying its own dispatch latency;
* :mod:`multihost` — the jax.distributed-shaped multi-host spec
  (documented + stubbed behind FABRIC_MOD_TPU_SHARDS).
"""
from fabric_mod_tpu.sharding.shardmap import ShardMap          # noqa: F401
from fabric_mod_tpu.sharding.router import (                   # noqa: F401
    ChannelShardRouter, ChannelVerifyHandle)
from fabric_mod_tpu.sharding.verifyservice import (            # noqa: F401
    CrossChannelVerifyService)
from fabric_mod_tpu.sharding.multihost import multihost_spec   # noqa: F401
