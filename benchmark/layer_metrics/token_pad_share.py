"""Host to device link: the share of the token wire that is padding: 100 x
(1 - ``text.tokens`` / ``text.token_slots``), the counters of the tokens
hashed and of the id slots shipped (three a word, the last word's spare
lanes and the pad to the size class included) over the trains of the
process.  A program without the counters reports nothing."""

LAYER = "host to device link"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_wall_s"

TOKENS, SLOTS = "text.tokens", "text.token_slots"


def read(ctx):
    from transmogrifai_tpu.telemetry import REGISTRY
    counters = REGISTRY.counters()
    if not ctx.get("trains") or not counters.get(SLOTS):
        return None
    return 100.0 * (1.0 - counters.get(TOKENS, 0) / counters[SLOTS])
