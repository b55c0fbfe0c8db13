"""The device as JAX reports it, and the refusal to run without one."""
from __future__ import annotations

from .loader import BenchError


def find_devices(chips, rehearse=False):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise BenchError("JAX found no accelerator (%s %s): nothing was run"
                         % (devs[0].platform, devs[0].device_kind))
    if len(devs) < chips:
        raise BenchError("the cell asks for %d chips, JAX has %d" % (chips, len(devs)))
    return devs[:chips]


def describe(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs, program_bytes=0):
    """Peak bytes on the fullest of the chips used.

    The allocator's ``peak_bytes_in_use`` does not count what a running
    program holds besides its arguments (PR 21 read 0.39 GB where the
    compiler's analysis says 2.65 GB; PR 24 read 0.81 GB for a step that holds
    8.67 GB). ``program_bytes`` is that share, from the compiler's
    ``memory_analysis()`` of the very program the window drives: temporaries,
    and outputs that alias no argument. The peak is the larger of the
    allocator's peak and what is live now plus the program's share."""
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(max(int(st.get("peak_bytes_in_use", 0)),
                         int(st.get("bytes_in_use", 0)) + int(program_bytes)))
    return max(peaks)


def memory_stats(devs):
    return [dict(d.memory_stats() or {}) for d in devs]


def program_share(compiled):
    """What a compiled program holds on one device besides its arguments."""
    m = compiled.memory_analysis()
    return {"temporaries": int(m.temp_size_in_bytes),
            "arguments": int(m.argument_size_in_bytes),
            "outputs": int(m.output_size_in_bytes),
            "aliased": int(m.alias_size_in_bytes),
            "beside_arguments": int(m.temp_size_in_bytes + m.output_size_in_bytes
                                    - m.alias_size_in_bytes)}


def must_compile_nothing(compile_counters):
    """``compile_counters``: the program's compile counters over the lowering
    whose memory is read. That lowering is made from the arguments of a real
    call of the step, so the executable is the one that ran: already in this
    process, or read back from the persistent cache. A compile there means
    another program was built and sized: a fault, not a reading."""
    built = compile_counters["compiles"] - compile_counters["cache_hits"]
    if built > 0 or compile_counters["cache_misses"] > 0:
        raise BenchError(
            "the step lowered for memory_analysis() is not the program the window ran: "
            "%d compiled anew, %d missed in the compile cache (benchmark/models/ records "
            "the step's own call; has the entry point changed under it?)"
            % (built, compile_counters["cache_misses"]))
