from repro.distributed import rules  # noqa: F401
from repro.distributed.act_sharding import activation_policy, constrain  # noqa: F401
