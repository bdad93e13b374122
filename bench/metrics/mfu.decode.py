"""Traced decode steps' share of the chip's peak."""
from harness import readers

read = readers.mfu("decode")
