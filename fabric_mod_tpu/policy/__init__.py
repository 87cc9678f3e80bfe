"""L2 signature-policy engine.

Compile SignaturePolicyEnvelope trees to evaluators (cauthdsl), parse
the "AND('Org1.member', ...)" DSL (policydsl), organize policies into
the channel's hierarchical manager (manager), and evaluate application
endorsement policies (application).  Evaluation is two-phase so a
block's worth of policy checks share ONE device batch-verify —
see cauthdsl.py's module docstring.
"""
from fabric_mod_tpu.policy.cauthdsl import (  # noqa: F401
    BatchCollector, CompiledPolicy, PendingEval, PolicyError)
from fabric_mod_tpu.policy.policydsl import DslError, from_string  # noqa: F401
from fabric_mod_tpu.policy.manager import (  # noqa: F401
    ImplicitMetaPolicyObj, PolicyManager, compile_policy_bytes,
    policy_from_proto)
from fabric_mod_tpu.policy.application import ApplicationPolicyEvaluator  # noqa: F401
