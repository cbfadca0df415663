from repro.runtime.actor import Actor, ActorSpec, build_actors
from repro.runtime.chaos import (DelayEdge, DropAck, DuplicateReq, FaultPlan,
                                 KillWorker, WorkerError, WorkerKilled)
from repro.runtime.messages import Ack, Req, make_actor_id, parse_actor_id
from repro.runtime.pipeline import (ActorPipelineExecutor,
                                    ServePipelineExecutor,
                                    TrainPipelineExecutor, analyze,
                                    pipeline_specs, plan_registers,
                                    serve_stage_actor_specs, stage_actor_specs,
                                    train_stage_actor_specs)
from repro.runtime.scheduler import CommModel, SimResult, Simulator, simulate
from repro.runtime.snapshot import (latest_snapshot, list_snapshots,
                                    load_snapshot)
from repro.runtime.threaded import ThreadedRuntime
