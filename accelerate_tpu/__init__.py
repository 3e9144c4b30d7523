__version__ = "0.1.0"

from .accelerator import Accelerator
from .state import AcceleratorState, GradientState, PartialState
from .logging import get_logger
from .modeling import Model, PreparedModel
from .optimizer import AcceleratedOptimizer, GradScaler
from .scheduler import AcceleratedScheduler
from .data_loader import SimpleDataLoader, prepare_data_loader, skip_first_batches
from .local_sgd import LocalSGD
from .launchers import debug_launcher, notebook_launcher
from .fault_tolerance import PREEMPTED_EXIT_CODE, PreemptionHandler, Supervisor
from .generation import GenerationConfig, Generator, generate
from .hooks import (
    CpuOffload,
    ModelHook,
    SequentialHook,
    UserCpuOffloadHook,
    add_hook_to_module,
    cpu_offload_with_hook,
    remove_hook_from_module,
)
from .tracking import GeneralTracker
from .telemetry import MetricsRegistry, ProfilerManager, StepTimeline, TrackerBridge
from .utils import (
    DataLoaderConfiguration,
    DeepSpeedPlugin,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    MegatronLMPlugin,
    ParallelismConfig,
    ProjectConfiguration,
    SequenceParallelPlugin,
    find_executable_batch_size,
)
