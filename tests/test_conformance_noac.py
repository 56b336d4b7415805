"""Engine conformance on the benchmark's NOAC shapes: every route of
``core.engines.mine`` against the paper oracle and the batch route
(``_conformance.py``; the prime shapes are in
``test_conformance_prime.py``)."""
import pytest

import _conformance as C


@pytest.mark.parametrize("route", C.ROUTES)
@pytest.mark.parametrize("name", ["ratings_d5_noac", "half_stars_d10_noac",
                                  "float_lane_noac"])
def test_route_conforms(name, route):
    C.check_route(name, route)
