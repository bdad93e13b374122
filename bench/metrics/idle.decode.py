"""Device idle share over traced decode steps (profiler trace)."""
from harness import readers

read = readers.idle("decode")
