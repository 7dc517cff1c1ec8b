"""The search kind ``RandomizedSearchCV``: the object a user hands to
``train()``, and the parameters sklearn's own sampler draws for it from the
seed (what the expansion is held to). A traffic file's
``param_distributions`` gives each parameter as a list of choices or as
``{"dist": <name in scipy.stats>, "args": [...]}``."""

from __future__ import annotations

from typing import Any, Dict, List


def distributions(spec: Dict[str, Any]) -> Dict[str, Any]:
    import scipy.stats

    return {name: list(d) if isinstance(d, list)
            else getattr(scipy.stats, d["dist"])(*d["args"]) for name, d in spec.items()}


def sampler_state(seed: int) -> int:
    return int(seed) % (2**31 - 1)


def build(estimator, traffic: Dict[str, Any], seed: int):
    from sklearn.model_selection import RandomizedSearchCV

    return RandomizedSearchCV(
        estimator, distributions(traffic["param_distributions"]),
        n_iter=int(traffic["n_iter"]), cv=int(traffic["cv"]), random_state=sampler_state(seed))


def expected(traffic: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    from sklearn.model_selection import ParameterSampler

    return list(ParameterSampler(distributions(traffic["param_distributions"]),
                                 n_iter=int(traffic["n_iter"]),
                                 random_state=sampler_state(seed)))
