"""Starting jax's profiler the way every traced run does: the Python
tracer off (on, it records every Python call - 400,000 host events in half
a second - and slows the host it is supposed to watch), the host tracer on
(it carries the TraceAnnotation spans), no HLO proto in the trace."""
import shutil


def start(trace_dir):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop():
    import jax
    jax.profiler.stop_trace()
