from llm_fine_tune_distributed_tpu.observe.xla import importing

# the trainer pulls in checkpoints.py and with it orbax, whose logging imports google.cloud.logging: the dearest
# import of a process's start (PERF.md section 5); while set-up lasts it is the span `import`
with importing(__name__):
    from llm_fine_tune_distributed_tpu.train.state import TrainState  # noqa: F401
    from llm_fine_tune_distributed_tpu.train.trainer import SFTTrainer  # noqa: F401
