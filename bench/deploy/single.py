"""The ``single`` deployment: one attribute-sorted RNSG index of the whole
corpus on one chip, served by ``RFANNEngine``.  A configuration without a
``layout`` is this kind.

The harness finds a deployment by its kind (``bench/deploy/<kind>.py``) and
calls ``build``, ``engine`` and ``warm_up`` in that order."""
from bench import data


def build(cell, corpus, devices):
    """The index, on the default device (``devices`` holds that one chip)."""
    from repro.core.rfann import RNSGIndex
    return RNSGIndex.build(corpus.vecs, corpus.attrs, **cell.cfg["build"])


def engine(cell, index):
    from repro.serving.engine import RFANNEngine
    return RFANNEngine(index, k=cell.cfg["k"], ef=cell.cfg["ef"],
                       **cell.cfg["engine"])


def warm_up(cell, index, tr) -> None:
    """Compile every shape the traffic can reach, outside the window: the
    scan at every power-of-two bucket up to the planner's scan ceiling and
    every padded batch size, and the beam at every padded batch size."""
    cfg, mix = cell.cfg, cell.mix
    k, ef, mb = cfg["k"], cfg["ef"], int(cfg["engine"]["max_batch"])
    pads = [1 << i for i in range(mb.bit_length()) if 1 << i <= mb]
    qv = tr.corpus.queries[:mb]
    srt = tr.attrs_sorted
    levels = sorted(set(int(v) for v in mix["levels"]))

    def search(count, rg, plan):
        index.search(qv[:count], rg[:count], k=k, ef=ef, plan=plan)

    bucket = 64
    while bucket <= index.planner.max_scan_len:
        rg = data.rank_window(srt, bucket / len(srt), tr.r, mb)
        for p in pads:
            search(p, rg, "scan")
        bucket *= 2
    for level in {levels[0], levels[-1]}:
        rg = data.rank_window(srt, 2.0 ** -level, tr.r, mb)
        for p in pads:
            search(p, rg, "beam")
