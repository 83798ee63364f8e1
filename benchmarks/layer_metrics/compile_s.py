"""compile_s — layer: XLA / Mosaic / compile cache. Seconds inside
compilations (loads from the persistent cache included) during set-up,
from JAX's `backend_compile_duration` monitoring event."""


def read(trace, facts):
    return facts.get('compile_s')
