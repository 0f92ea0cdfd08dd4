"""The fleet MSO program's time per step that served asks, read as
``mso_ms_per_step`` reads it (the same spans, the same steps), in the
open-loop cells, where a step's batch is whatever arrived while the last
one ran and the step's time is what the next arrivals queue behind."""
from bench.harness import load_reader

read = load_reader("mso_ms_per_step")
