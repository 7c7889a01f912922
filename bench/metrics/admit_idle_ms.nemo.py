"""admit_idle_ms, in the Nemotron-H decode-heavy cell, where admissions
come between decode steps that every slot waits on. The same reading as
admit_idle_ms."""
from bench.metrics.admit_idle_ms import read  # noqa: F401
