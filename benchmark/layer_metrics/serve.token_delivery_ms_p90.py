"""90th percentile (nearest rank) of ``held_ms`` over the
``next_tokens`` calls (the program's ``replica.call`` spans) that the
router handed over in the measured window and that returned at least
one token: the age, when the call returned, of the oldest token it
returned, counted from the engine's hand-out that put it on the stream
(``InferenceEngine._hand_out`` stamps each). How long a token that the
engine has made waits in the replica before a caller has it.
``[loop_gap]`` prints what the loop's thread waits between a round's
``engine.deliver`` and the next ``engine.admit``, split by how many
replica calls returned meanwhile. None on a program without these
spans."""
from benchmark import request_path
from benchmark.spans import percentile


def read(ctx):
    held = [r[5]["held_ms"] for r in
            request_path.window_calls(ctx, "next_tokens")
            if r[5].get("tokens") and r[5].get("held_ms") is not None]
    if not held:
        return None
    request_path.say_loop_gap(ctx)
    return percentile(held, 90)
