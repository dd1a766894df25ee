"""model_step.prefill_ms_per_step.tails: see ``bench.programs.prefill_ms_per_step``."""
from bench.programs import prefill_ms_per_step as read  # noqa: F401
