from analytics_zoo_tpu.core.config import ZooConfig  # noqa: F401
from analytics_zoo_tpu.core.context import (  # noqa: F401
    HostRoster,
    ZooContext,
    describe_devices,
    enable_compile_cache,
    get_zoo_context,
    init_zoo_context,
    make_mesh,
    set_zoo_context,
)
from analytics_zoo_tpu.core.triggers import (  # noqa: F401
    And,
    EveryEpoch,
    MaxEpoch,
    MaxIteration,
    MaxScore,
    MinLoss,
    Or,
    SeveralIteration,
    Trigger,
    TriggerState,
)
