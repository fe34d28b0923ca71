# engine.py     — wave scheduler: same-length prompt batches, lockstep decode
# continuous.py — slot arena: continuous batching with per-slot lengths
# paged.py      — block pool + block tables: paged KV with chunked prefill
#                 (packed token steps by default; lockstep via packed=False)
# admission.py  — opt-in overload robustness: priority classes, deadlines,
#                 bounded queue + backpressure, preemption policy
# chaos.py      — seeded fault injector + engine invariant checker
# telemetry.py  — request-lifecycle tracing (TTFT/TPOT/E2E percentiles),
#                 step-phase profiler (serve.* spans in the jax.profiler
#                 trace), unified schema-versioned snapshot, open-loop
#                 arrival driver
from repro.serve.admission import (AdmissionConfig, AdmissionQueue,
                                   QueueFull, RobustnessCounters,
                                   choose_victim)
from repro.serve.chaos import ChaosMonkey, assert_drained, check_invariants
from repro.serve.continuous import ContinuousEngine
from repro.serve.engine import (Request, ServeEngine, kv_cache_byte_stats,
                                kv_cache_bytes, sample_tokens)
from repro.serve.paged import (BlockAllocator, BlockPoolExhausted,
                               PagedEngine, PrefixTrie, pack_slot_ids,
                               packed_write_positions, prefix_chunk,
                               schedule_step_tokens)
from repro.serve.telemetry import (MetricsRegistry, RequestTrace,
                                   StepProfiler, Telemetry, drive_open_loop,
                                   format_snapshot, make_snapshot,
                                   percentile)
