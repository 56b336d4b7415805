"""Engine conformance on the benchmark's prime shapes: every route of
``core.engines.mine`` against the paper oracle and the batch route
(``_conformance.py``; the NOAC shapes are in
``test_conformance_noac.py``)."""
import pytest

import _conformance as C


@pytest.mark.parametrize("route", C.ROUTES)
@pytest.mark.parametrize("name", ["folksonomy_prime", "dense_4d_prime",
                                  "frames_prime"])
def test_route_conforms(name, route):
    C.check_route(name, route)
