"""The traffic generator, without a chip."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = sorted((Path(__file__).parents[1] / "traffic").glob("*.json"))


def mix(name="chat-zipf"):
    return json.loads((Path(__file__).parents[1] / "traffic" / f"{name}.json").read_text())


def sig(reqs):
    return [(r.due_s, r.tenant, r.max_new_tokens, r.prompt.tobytes()) for r in reqs]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    m = json.loads(path.read_text())
    a = traffic.generate(m, 49152, 2**31 + 5, 10.0)
    b = traffic.generate(m, 49152, 2**31 + 5, 10.0)
    assert sig(a) == sig(b)
    c = traffic.generate(m, 49152, 6, 10.0)
    assert sig(a) != sig(c)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_offers_the_same_work(path):
    """Sizes and gaps come from the mix's shape seed: another run seed
    permutes them."""
    m = json.loads(path.read_text())
    a = traffic.generate(m, 49152, 1, 10.0)
    b = traffic.generate(m, 49152, 2, 10.0)
    assert len(a) == len(b) > 0
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    assert all(r.due_s < 10.0 for r in a + b)


def test_clipped_lognormal_matches_its_parameters():
    rng = np.random.default_rng(0)
    spec = {"median": 256, "sigma": 1.0, "min": 16, "max": 1024}
    x = traffic.clipped_lognormal(rng, spec, 200_000)
    assert x.min() == 16 and x.max() == 1024
    assert np.median(x) == pytest.approx(256, rel=0.02)
    # P(X > 1024) = P(Z > ln 4 / 1) = 0.0828; P(X < 16) = P(Z < -ln 16) = 0.00278
    assert np.mean(x == 1024) == pytest.approx(0.0828, abs=0.003)
    assert np.mean(x == 16) == pytest.approx(0.00278, abs=0.0006)


def test_zipf_tenant_frequencies():
    m = mix()
    m["arrival"]["rate_per_s"] = 2000.0
    reqs = traffic.generate(m, 1000, 3, 50.0)
    counts = np.bincount([r.tenant for r in reqs], minlength=m["tenants"]["count"])
    ranked = np.sort(counts)[::-1]
    w = traffic.zipf_weights(m["tenants"]["count"], 1.0)
    assert ranked[0] / len(reqs) == pytest.approx(w[0], rel=0.05)
    assert ranked[:10].sum() / len(reqs) == pytest.approx(w[:10].sum(), rel=0.03)


def test_poisson_rate():
    m = mix()
    gaps, _, _ = traffic.shapes(m, 1000.0, rate=20.0)
    assert len(gaps) / 1000.0 == pytest.approx(20.0, rel=0.03)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, rel=0.05)
