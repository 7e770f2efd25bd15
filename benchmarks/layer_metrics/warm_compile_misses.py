"""Programs built during set-up that the persistent cache did not hold:
`jax.monitoring` backend compilations minus cache hits, counted by the
program's `collect_cache_events` from process start to the window's opening.
Small programs that JAX never persists count on every run."""
LAYER = 'entry and compile cache'
UNIT = 'count'
MOVES = 'setup_s'


def read(run: dict):
    events = run.get('setup_events')
    if events is None:
        return None
    built = sum(v for k, v in events.items() if k.endswith('backend_compile_duration'))
    hits = sum(v for k, v in events.items() if k.endswith('cache_hits'))
    return float(built - hits)
