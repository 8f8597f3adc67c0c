"""keyBy -> event-time tumbling window -> sum, through the public entry."""

from flink_tpu.core.time import TimeCharacteristic


def build(env, source, sink, job: dict) -> None:
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    (
        env.add_source(source)
        .key_by(lambda c: c["key"])
        .time_window(job["window"]["size_ms"])
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )
