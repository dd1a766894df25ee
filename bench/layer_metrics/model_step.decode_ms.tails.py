"""model_step.decode_ms.tails: see ``bench.programs.decode_ms``."""
from bench.programs import decode_ms as read  # noqa: F401
