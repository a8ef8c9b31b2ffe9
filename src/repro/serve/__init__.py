"""repro.serve — continuous-batching serving with prefill/decode
disaggregation and optimistic per-session trust.  See README.md in this
directory for the scheduler lifecycle and the batched per-tick Merkle
commitment scheme."""
from repro.serve.engine import ServingEngine, SessionRecord
from repro.serve.scheduler import SlotScheduler, SlotState

__all__ = ["ServingEngine", "SessionRecord", "SlotScheduler", "SlotState"]
