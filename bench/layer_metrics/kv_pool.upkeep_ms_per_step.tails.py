"""kv_pool.upkeep_ms_per_step.tails: see ``bench.programs.pool_upkeep_ms``."""
from bench.programs import pool_upkeep_ms as read  # noqa: F401
