"""Device ms a step under `glm.moe.route`: router, top-k, sort, gather, weighted scatter; the memory-bound part.
Every language-model family whose step runs under these scopes has it: the reader asks for no family."""
LAYER = 'experts'
UNIT = 'ms'
MOVES = 'train_img_per_s'


def read(run: dict):
    from benchmarks.harness import lm_readers
    return lm_readers.READERS['moe_route_device_ms.train'].read(run)
