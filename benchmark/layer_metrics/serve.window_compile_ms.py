"""Milliseconds the serving process spent compiling inside the measured
window (jax's own monitoring events, benchmark/compile_watch.py). The
engine's programs are built in set-up; what is left is what the engine
compiles as it goes: today one eager ``jnp.concatenate`` in its loop
for each new combination of a burst's chunks and first tokens, too
short (40 ms) for the persistent cache to keep unless a stalled host
or the profiler stretched one past jax's threshold of a second; one
read from the cache counts like a compilation. The loop stands still
meanwhile."""


def read(ctx):
    return 1e3 * ctx["window_compile_s"]
