"""scheduler.queue_wait_ms.tails: see ``bench.readers.queue_wait_ms``."""
from bench.readers import queue_wait_ms as read  # noqa: F401
