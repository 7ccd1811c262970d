"""Plain float32 references: ``loss(weights, batch, model, params)`` and
``tolerance(model)``, each tolerance written with its reason."""
