"""The ``range_sharded`` deployment: one RNSG corpus split by attribute
range into ``layout.shards`` contiguous shards, each shard's graph on its
own chip of a ("data",) mesh, served by ``RFANNEngine`` through
``DistributedRFANN``'s mesh path (``MeshSubstrate``).

The harness finds a deployment by its kind (``bench/deploy/<kind>.py``) and
calls ``build``, ``engine`` and ``warm_up`` in that order."""
import numpy as np


def build(cell, corpus, devices):
    """The sharded index, shard ``s`` built on and held by ``devices[s]``.
    A program whose mesh path cannot compile its programs ahead of the
    window (no ``MeshSubstrate.warm_up``) is refused before the build."""
    from jax.sharding import Mesh

    from repro.search import MeshSubstrate
    from repro.serving.distributed import DistributedRFANN
    if not hasattr(MeshSubstrate, "warm_up"):
        raise RuntimeError("this program's mesh path has no warm_up: its "
                           "compiled programs are not bounded")
    shards = int(cell.cfg["layout"]["shards"])
    mesh = Mesh(np.asarray(devices[:shards]), ("data",))
    return DistributedRFANN(corpus.vecs, corpus.attrs, n_shards=shards,
                            mesh=mesh, **cell.cfg["build"])


def engine(cell, index):
    from repro.serving.engine import RFANNEngine
    return RFANNEngine(index, k=cell.cfg["k"], ef=cell.cfg["ef"],
                       **cell.cfg["engine"])


def warm_up(cell, index, tr) -> None:
    """Compile every mesh program a batch of up to ``max_batch`` queries
    can reach (``MeshSubstrate.warm_up``), outside the window, and log how
    many there are and each chip's memory after them."""
    import jax

    from bench import harness
    cfg, eng = cell.cfg, cell.cfg["engine"]
    ms = index.mesh_substrate
    compiles, counting = [0], [True]

    def count(event, duration, **_):
        if counting[0] and event in harness.COMPILE_EVENTS:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(count)
    ms.warm_up(k=cfg["k"], ef=cfg["ef"], max_batch=int(eng["max_batch"]),
               beam_width=int(eng["beam_width"]),
               precision=eng["precision"])
    counting[0] = False
    harness.log(f"mesh warm-up: {len(ms._fns)} mesh programs, "
                f"{compiles[0]} compiles")
    for i, dv in enumerate(index.mesh.devices.flat):
        st = dv.memory_stats() or {}
        harness.log(f"HBM chip {i}: peak {st.get('peak_bytes_in_use', 0)} "
                    f"bytes, in use {st.get('bytes_in_use', 0)} bytes")
