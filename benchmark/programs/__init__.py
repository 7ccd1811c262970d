"""Program builders: ``build(model, params)`` and ``batch(model, params, rng)``."""
