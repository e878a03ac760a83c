"""Fault injection and chaos tooling for the CodedFedL runtime.

CodedFedL's coding layer is built to compensate for *missing* client
work (stragglers, erasures — `repro.net`).  This subsystem injects the
*wrong*-work failure modes a real MEC deployment adds on top — non-finite
client gradient returns, stale-update replay, corrupted parity uploads,
checkpoint truncation/bit-flips, and service block crashes — so the
runtime's graceful-degradation machinery (the compiled step's
non-finite guard, `fed_runtime._guard_and_sum`, which every scheme's
round shares; `checkpoint.io`'s digest verification;
`launch.service`'s retry/backoff) can be exercised deterministically.

`FaultProfile` declares a fault mix the way `repro.net.channel
.ChannelProfile` declares network dynamics: a frozen, JSON-round-tripping
dataclass addressable by name (`FAULT_PROFILES`) from
``ExperimentSpec.fault_profile``, with per-knob overrides in
``fault_params``.  Per-round/per-client fault draws come from a dedicated
RNG stream (`sample_fault_rows`) that is independent of both the delay
and channel-trace streams, so toggling faults never shifts the network
realization a run faces.
"""
from repro.faults.profile import (FAULT_PROFILES, FaultProfile,  # noqa: F401
                                  get_fault_profile)
from repro.faults.inject import (CODE_CLEAN, CODE_INF, CODE_NAN,  # noqa: F401
                                 CODE_STALE, InjectedCrashError,
                                 bitflip_file, corrupt_checkpoint,
                                 sample_fault_rows, truncate_file)

__all__ = [
    "FaultProfile", "FAULT_PROFILES", "get_fault_profile",
    "InjectedCrashError", "sample_fault_rows", "corrupt_checkpoint",
    "truncate_file", "bitflip_file",
    "CODE_CLEAN", "CODE_NAN", "CODE_INF", "CODE_STALE",
]
