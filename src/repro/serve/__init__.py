from repro.serve.engine import (
    ROUTING,
    ClosedLoopResult,
    ClosedLoopServer,
    FusedServingStep,
    ServePolicy,
    ServeResult,
    ServeTables,
    ServingEngine,
    serve_policy_step,
    tokens_from_strips,
)
