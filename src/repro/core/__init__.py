"""Core: the paper's push-based data delivery framework (faithful layer).

Public API re-exports.
"""
from repro.core.arima import ARIMA, ARIMAOrder, predict_next_timestamp
from repro.core.cache import (IntervalLRUState, IntLFUState, IntLRUState,
                              LFUCache, LRUCache, chunk_bounds_bulk,
                              chunks_for_range, make_cache,
                              make_int_cache_state)
from repro.core.engine import IntervalVDCSimulator, VectorVDCSimulator
from repro.core.interval_store import FlatIntervalState
from repro.core.classify import (classify_request_type, classify_users,
                                 fresh_duplicate_bytes, summarize_trace)
from repro.core.delivery import (HPMAdapter, MD1Adapter, MD2Adapter,
                                 NoPrefetch, PeerFetchRange,
                                 coalesce_peer_ranges, make_prefetcher,
                                 select_peer_sources)
from repro.core.fpgrowth import RulePredictor, association_rules, frequent_itemsets
from repro.core.hpm import (BatchedHPMPlanner, HybridPrefetcher, PrefetchOp,
                            build_rule_transactions)
from repro.core.kmeans import kmeans
from repro.core.markov import MarkovPredictor
from repro.core.mining import MeshRulePredictor
from repro.core.placement import PlacementEngine, select_hub
from repro.core.simulator import (OutcomeAggregate, SimConfig, SimResult,
                                  VDCSimulator, run_strategy)
from repro.core.streaming import StreamingEngine
from repro.core.trace import (GAGE_PROFILE, OOI_PROFILE, ObjectGrid, Request,
                              RequestArrays, RequestList,
                              StreamingRequestSource,
                              StreamingTraceSynthesizer, TraceGenerator,
                              make_trace, requests_to_arrays)

__all__ = [n for n in dir() if not n.startswith("_")]
