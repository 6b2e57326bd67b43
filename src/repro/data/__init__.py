from repro.data.synthetic import DATASETS, DatasetSpec, make_dataset

__all__ = ["DATASETS", "DatasetSpec", "make_dataset"]
