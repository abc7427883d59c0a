"""End-to-end behaviour: the paper's orchestration (Fig. 1 stages)."""
from repro.core import CoopConfig, Sptlb, generate_cluster


def test_sptlb_full_pipeline_stages():
    """Fig. 1 stages produce a coherent decision record."""
    cluster = generate_cluster(num_apps=200, seed=3)
    decision = Sptlb(cluster).balance("local",
                                      config=CoopConfig(max_rounds=15))
    pm = decision.projected
    assert pm.util_frac.shape == (5, 2)
    assert pm.num_moved == len(pm.moved_apps)
    assert sum(pm.transitions.values()) == pm.num_moved
    assert decision.violations.ok
    assert decision.network_p99_ms >= 0
    assert 0 <= decision.difference_to_balance <= 1.5
