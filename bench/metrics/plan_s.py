"""Host seconds from the extracted sites to a finished tile plan."""
from harness import readers

read = readers.plan_seconds
