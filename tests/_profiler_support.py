"""Record a JAX profiler trace around a call and read back what the host
recorded: the program's spans with their stats, and the names of the XLA
programs that ran (on the CPU every op event carries its module as the
`hlo_module` stat)."""
import contextlib
import glob
import os


@contextlib.contextmanager
def recording(trace_dir):
    """A profiler session on `trace_dir` (host spans only, no Python
    tracer); yields a list that holds the `ProfileData` once it stops."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = []
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out.append(ProfileData.from_file(files[0]))


def host_events(pd, prefix=""):
    """(name, start_ns, end_ns, stats) of the host plane's events whose
    name starts with `prefix`, in time order."""
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for e in line.events
           if e.name.startswith(prefix)]
    return sorted(evs, key=lambda e: e[1])


def module_names(pd):
    """The XLA programs whose ops ran while recording."""
    return {s.get("hlo_module") for _, _, _, s in host_events(pd)} - {None}
