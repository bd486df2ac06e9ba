"""Programs compiled or read back from the persistent compile cache in
set-up (JAX's backend-compile events)."""


def read(ctx):
    c = ctx.get("compile_setup")
    return None if c is None else c["programs"]
