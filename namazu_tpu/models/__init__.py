"""Search-plane models: the genetic algorithm over schedule genomes and the
learned reward surrogate."""

#: The search knobs and the ONE place their defaults are written, under
#: the names they travel by (``TPUSearchPolicy._search_params`` -> the
#: sidecar wire -> ``models.search.build_search_from_params``). The
#: policy's attributes start from this table, the builder fills what a
#: caller left out from it, and ``SearchConfig``'s field defaults read
#: it — so the three cannot disagree. Kept here, free of imports, so the
#: control plane reads it without importing JAX.
SEARCH_DEFAULTS = {
    "H": 256,  # hint buckets (genome length)
    "L": 0,  # trace-length cap; 0 = encode full traces (no drop)
    "K": 256,  # precedence pairs (feature dimension)
    "population": 4096,  # total genomes across all islands
    "migrate_k": 8,  # elite rows sent round the island ring a generation
    "fused_chunk": 16,  # generations per dispatch of the island step
    "seed": 0,
    "max_interval": 0.1,  # seconds; the genome's delay range
    "max_fault": 0.0,  # per-hint fault probability cap (0 = off)
    "surrogate_topk": 16,  # 0 = fitness argmax only (no surrogate)
    # novelty anneal: explore at full w_novelty until the
    # failure archive holds this many DISTINCT signatures (0 = static
    # weights), then scale novelty down, never below the floor
    "min_failure_signatures": 0,
    "novelty_floor": 0.25,
    # causality guidance (doc/search.md)
    "guidance": False,
    "guidance_bonus": 0.5,
    "guidance_width": 0,  # 0 = guidance.DEFAULT_WIDTH
    "guidance_window": 0,  # 0 = guidance.DEFAULT_WINDOW
    "release_mode": "delay",  # "delay" | "reorder" (BASELINE config 3)
    # fitness weights (ops/schedule.py ScoreWeights)
    "w_novelty": 1.0,
    "w_bug": 1.0,
    "w_delay_cost": 0.01,
    "w_fault_cost": 0.05,
    "tau": 0.005,  # precedence smoothing, seconds
    "reorder_gap": 0.002,
    "reorder_window": 0.05,
    "devices": None,  # None = every device of the process
}


def refuse_search_backend(stated) -> None:
    """``search_backend`` went with the MCTS backend (PR 48): there is
    one search, the island GA. ``"ga"`` (a config not yet edited, or the
    params of an older policy over the sidecar's wire) is that search;
    any other value is refused here, by ``TPUSearchPolicy.load_config``
    and by ``models.search.build_search_from_params`` alike — a hunt
    that asked for MCTS must not silently run the GA."""
    if stated not in (None, "ga"):
        raise ValueError(
            f"search_backend = {stated!r}: the MCTS backend was removed "
            "and the island GA is the one search (PARITY.md, config 5); "
            "drop the key to search with it")
