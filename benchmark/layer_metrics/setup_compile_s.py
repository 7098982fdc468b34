"""Compile reuse: seconds inside XLA backend compilation (or retrieval from
the persistent cache) during set-up."""

LAYER = "compile reuse"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    return ctx["setup"].get("compile_s")
