"""Batcher admission: ``admit_wait_ms`` in the long-prompt cell, whose
tails are read per layer (``ttft_p95_ms.longprompt``), so that it moves
the cell's end-to-end rate."""
from harness import readers

read = readers.load_reader("admit_wait_ms")
