from .mesh import n_workers_of, worker_axes_of
from .publish import ReplicaFleet, publish_trajectory, trainer_rounds
