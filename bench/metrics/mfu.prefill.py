"""Traced prefill steps' share of the chip's peak."""
from harness import readers

read = readers.mfu("prefill")
