"""Device time per decode step at baseline tiles over the agent's plan."""
from harness import readers

read = readers.plan_gain("decode")
