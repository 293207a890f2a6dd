"""90th percentile (nearest rank) of ``waited_ms`` over the program's
``replica.call`` spans whose hand-over by the router fell in the
measured window, every method together: from
``_DeploymentState.submit`` / ``submit_sticky`` handing the call to the
replica actor until the deployment's method started on one of the
replica's threads, i.e. the wait in the actor's inbox. The program
writes it where router and replica share a process (one clock), which
they do here. ``[replica_calls]`` prints the wait and the run time by
method; ``[request_path]`` cuts the TTFT of the window's requests into
the pieces that the program's spans give (``benchmark/request_path.py``).
None on a program without these spans."""
from benchmark import request_path
from benchmark.spans import percentile


def read(ctx):
    calls = request_path.window_calls(ctx)
    if not calls:
        return None
    request_path.say_replica_calls(calls)
    request_path.say_request_path(ctx)
    return percentile([r[5]["waited_ms"] for r in calls], 90)
