import asyncio


async def pump(gateway):
    with gateway.telemetry.span("pump.tick"):
        gateway.pump()
        await asyncio.sleep(0.001)


async def pump_ok(gateway):
    with gateway.telemetry.span("pump.tick"):
        gateway.pump()
    await asyncio.sleep(0.001)
