"""The benchmark's own contract checks in tier-1 (PERF.md section 7,
left over from PR 35): ``perfbench/tests/test_contract.py``, whole, with no
node and no device, so that a list or file of ``BENCHMARK.json`` that no
longer holds together fails the suite the driver runs, and not only
``perfbench/tests/``.

One of its cases predates the first configuration with a ``stake`` list
(``hub180``, PR 36): it holds every configuration to ``stake_each``. A
benchmark file may be changed only by a ``benchmark`` PR, so that case is
taken here over the configurations it was written for, and a configuration
with a list is held to its list instead.
"""

import conftest  # noqa: F401

import pytest

from perfbench.harness import corpus
from perfbench.tests import test_contract
from perfbench.tests.test_contract import *  # noqa: F401,F403  the cases, collected here

EQUAL = [n for n in test_contract.CONFIGS if "stake" not in test_contract.config_of(n)]
LISTED = [n for n in test_contract.CONFIGS if "stake" in test_contract.config_of(n)]


@pytest.mark.parametrize("name", EQUAL)
def test_a_configuration_without_a_stake_list_has_stake_each_for_all(name):
    config = test_contract.config_of(name)
    assert corpus.powers_of(config) == [config["stake_each"]] * config["validators"]


@pytest.mark.parametrize("name", LISTED)
def test_a_configuration_with_a_stake_list_is_run_at_that_list(name):
    config = test_contract.config_of(name)
    assert "stake_each" not in config  # one statement of the stake, not two
    assert corpus.powers_of(config) == config["stake"]
