"""The image loader's decode worker: one process with its own interpreter (and
its own interpreter lock), holding a copy of the dataset, which turns lists of
indices into raw uint8/float32 pixels and writes them down a pipe.

`loader._DecodePool` starts it as a script, `python decode_worker.py <result fd>`,
and NOT through `multiprocessing`: a spawned `multiprocessing` child imports the
parent's `__main__` (train.py: JAX, 15 s) and brings a resource tracker with its
semaphores. Run by path, nothing of `timm_tpu/__init__.py` is imported either:
`install_package_stubs` stands bare packages in for `timm_tpu`, `timm_tpu.data`
and `timm_tpu.resilience`, so unpickling the dataset imports the transform
modules (PIL, numpy) and never `jax`. A worker never touches a device.

Life cycle: the worker's stdin is a pipe only its parent holds the other end of.
A listener thread reads the epochs' index lists from it; when it reads end of
file (the parent closed the pool, left through `os._exit`, or was killed) it ends
the process at once, whatever the main thread is blocked in.

Frames on the result pipe: 16 bytes (`_FRAME`: length of a pickled head, length of
the raw pixels), the head, the pixels. Only the head is pickled.
"""
import os
import pickle
import struct
import sys
import time

_FRAME = struct.Struct('<QQ')
_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # .../timm_tpu


def install_package_stubs():
    """`timm_tpu`, `timm_tpu.data` and `timm_tpu.resilience` as bare packages: their
    submodules import as usual, their `__init__.py` (models, layers, device
    programs: JAX) never runs. `timm_tpu.resilience` gets the names of its two
    host-only modules, which the readers import from the package."""
    import types
    for name in ('timm_tpu', 'timm_tpu.data', 'timm_tpu.resilience'):
        stub = types.ModuleType(name)
        stub.__path__ = [os.path.join(os.path.dirname(_PACKAGE_ROOT), *name.split('.'))]
        sys.modules[name] = stub
    from timm_tpu.resilience import faultinject, retry
    for module in (faultinject, retry):
        for public in module.__all__:
            setattr(sys.modules['timm_tpu.resilience'], public, getattr(module, public))


def read_sample(dataset, idx: int):
    """`dataset[idx]` as every decode stage reads it: behind the fault injector's
    tick, transient I/O faults (OSError) riding jittered exponential backoff.
    What still raises is poison, for the caller's skip budget. (The imports are
    here because the worker installs its stubs after this module is loaded.)"""
    from timm_tpu.resilience.faultinject import get_fault_injector
    from timm_tpu.resilience.retry import retry_io

    def read():
        injector = get_fault_injector()
        if injector is not None and injector.io_error_tick():
            raise IOError(f'[fault-inject] sample read {idx}')
        return dataset[idx]

    return retry_io(read, retries=3, base_delay=0.05, desc=f'sample {idx}')


def seed_generators(seed: int, epoch: int, worker: int):
    """`data/transforms.py` and `data/auto_augment.py` draw from the global `random`
    and `np.random`: both are seeded from (seed, epoch, worker) at an epoch's
    start, so no two workers draw one stream and `--seed` decides every draw."""
    import random

    import numpy as np
    a, b = np.random.SeedSequence([seed & (2 ** 64 - 1), epoch, worker]).generate_state(2)
    random.seed(int(a))
    np.random.seed(int(b))


def write_frame(out, head: dict, pixels=None):
    head = pickle.dumps(head, pickle.HIGHEST_PROTOCOL)
    out.write(_FRAME.pack(len(head), 0 if pixels is None else pixels.nbytes))
    out.write(head)
    if pixels is not None:
        out.write(memoryview(pixels).cast('B'))
    out.flush()


def _read_exactly(raw, view) -> bool:
    """Fill `view` from the unbuffered file `raw`; False at end of file."""
    got = 0
    while got < len(view):
        n = raw.readinto(view[got:])
        if not n:
            return False
        got += n
    return True


def read_frame(raw):
    """-> (head, pixels as an array of the head's shape and dtype, or None), or
    None at end of file. The pixels are read straight into the array."""
    import numpy as np
    lengths = bytearray(_FRAME.size)
    if not _read_exactly(raw, memoryview(lengths)):
        return None
    head_len, pixel_len = _FRAME.unpack(lengths)
    head = bytearray(head_len)
    if not _read_exactly(raw, memoryview(head)):
        return None
    head = pickle.loads(head)
    if not pixel_len:
        return head, None
    pixels = np.empty(head['shape'], np.dtype(head['dtype']))
    if pixels.nbytes != pixel_len or not _read_exactly(raw, memoryview(pixels).cast('B')):
        return None
    return head, pixels


def _portable(exc: Exception) -> Exception:
    try:
        pickle.loads(pickle.dumps(exc))
        return exc
    except Exception:
        return RuntimeError(f'{type(exc).__name__}: {exc}')


def decode_chunk(dataset, idxs) -> tuple:
    """-> (head, pixels): the chunk's good samples stacked into one array (an
    AugMix sample's splits stacked first), their targets, and each poisoned
    position with its exception."""
    import numpy as np
    start = time.perf_counter_ns()
    images, targets, bad, splits = [], [], [], 0
    for pos, idx in enumerate(idxs):
        try:
            img, target = read_sample(dataset, idx)
        except Exception as e:
            bad.append((pos, _portable(e)))
            continue
        if isinstance(img, (tuple, list)):
            splits = len(img)
            img = np.stack(img)
        images.append(np.asarray(img))
        targets.append(target)
    pixels = np.stack(images) if images else None
    head = {'targets': targets, 'bad': bad, 'splits': splits, 'busy_ns': time.perf_counter_ns() - start}
    if pixels is not None:
        head.update(shape=pixels.shape, dtype=pixels.dtype.str)
    return head, pixels


def main():
    import queue
    import signal
    import threading
    sys.path[0] = os.path.dirname(_PACKAGE_ROOT)    # was this file's directory
    install_package_stubs()
    # a terminal's interrupt reaches the whole group: the parent decides when the workers end
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    commands = sys.stdin.buffer
    out = os.fdopen(int(sys.argv[1]), 'wb')
    init = pickle.load(commands)
    dataset, worker = pickle.loads(init['dataset']), init['worker']
    if init['fault_spec']:
        from timm_tpu.resilience.faultinject import set_fault_injector
        set_fault_injector(init['fault_spec'])
    epochs: 'queue.Queue' = queue.Queue()

    def listen():
        try:
            while True:
                epochs.put(pickle.load(commands))
        except EOFError:
            pass
        finally:
            os._exit(0)     # the parent is gone, or has closed the pool

    threading.Thread(target=listen, daemon=True).start()
    while True:
        seed, epoch, chunks = epochs.get()
        seed_generators(seed, epoch, worker)
        for idxs in chunks:
            write_frame(out, *decode_chunk(dataset, idxs))


if __name__ == '__main__':
    try:
        main()
    except BrokenPipeError:
        os._exit(0)         # the parent stopped reading: it is gone
