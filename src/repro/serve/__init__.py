from repro.serve.engine import (
    DONATED_CACHE,
    ROUTING,
    ClosedLoopResult,
    ClosedLoopServer,
    FusedServingStep,
    ServePolicy,
    ServeTables,
    ServingEngine,
    serve_policy_step,
    tokens_from_strips,
)
