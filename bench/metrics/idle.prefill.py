"""Device idle share over traced prefill steps (profiler trace)."""
from harness import readers

read = readers.idle("prefill")
