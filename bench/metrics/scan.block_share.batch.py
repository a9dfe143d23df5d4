"""scan.block_share.batch (%): candidate blocks the scan kernels streamed over
the blocks of every local partition's whole capacity (what a scan that
skips nothing streams), from the engine's ``lira_engine_scan_blocks_total``
and ``lira_engine_scan_blocks_dense_total``. None from a program that
does not count them."""


def read(run):
    from repro.obs.metrics import parse_exposition

    if run.registry is None or run.registry.get("lira_engine_scan_blocks_total") is None:
        return None
    series = parse_exposition(run.registry.render())

    def total(name):
        return sum(v for k, v in series.items() if k.split("{")[0] == name)

    dense = total("lira_engine_scan_blocks_dense_total")
    return 100.0 * total("lira_engine_scan_blocks_total") / dense if dense else None
