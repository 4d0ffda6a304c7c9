"""GENERATED FILE — do not edit by hand.

Regenerate with `python -m mmlspark_tpu.codegen` (the codegen
meta-test diffs this file against the registry — SURVEY.md §2.2).
"""

# flake8: noqa
_UNSET = object()

from mmlspark_tpu.automl.search import BestModel as _BestModel
from mmlspark_tpu.automl.search import FindBestModel as _FindBestModel
from mmlspark_tpu.automl.search import TuneHyperparameters as _TuneHyperparameters
from mmlspark_tpu.automl.search import TuneHyperparametersModel as _TuneHyperparametersModel
from mmlspark_tpu.cognitive.anomaly import BingImageSearch as _BingImageSearch
from mmlspark_tpu.cognitive.anomaly import DetectEntireSeries as _DetectEntireSeries
from mmlspark_tpu.cognitive.anomaly import DetectLastAnomaly as _DetectLastAnomaly
from mmlspark_tpu.cognitive.face import FindSimilarFace as _FindSimilarFace
from mmlspark_tpu.cognitive.face import GroupFaces as _GroupFaces
from mmlspark_tpu.cognitive.face import IdentifyFaces as _IdentifyFaces
from mmlspark_tpu.cognitive.face import VerifyFaces as _VerifyFaces
from mmlspark_tpu.cognitive.speech import SpeechToText as _SpeechToText
from mmlspark_tpu.cognitive.text import EntityDetector as _EntityDetector
from mmlspark_tpu.cognitive.text import KeyPhraseExtractor as _KeyPhraseExtractor
from mmlspark_tpu.cognitive.text import LanguageDetector as _LanguageDetector
from mmlspark_tpu.cognitive.text import NER as _NER
from mmlspark_tpu.cognitive.text import TextSentiment as _TextSentiment
from mmlspark_tpu.cognitive.text import Translate as _Translate
from mmlspark_tpu.cognitive.vision import AnalyzeImage as _AnalyzeImage
from mmlspark_tpu.cognitive.vision import DescribeImage as _DescribeImage
from mmlspark_tpu.cognitive.vision import DetectFace as _DetectFace
from mmlspark_tpu.cognitive.vision import OCR as _OCR
from mmlspark_tpu.cognitive.vision import TagImage as _TagImage
from mmlspark_tpu.core.pipeline import Pipeline as _Pipeline
from mmlspark_tpu.core.pipeline import PipelineModel as _PipelineModel
from mmlspark_tpu.explain.lime import ImageLIME as _ImageLIME
from mmlspark_tpu.explain.lime import TabularLIME as _TabularLIME
from mmlspark_tpu.explain.lime import TabularLIMEModel as _TabularLIMEModel
from mmlspark_tpu.explain.superpixel import SuperpixelTransformer as _SuperpixelTransformer
from mmlspark_tpu.featurize.clean import CleanMissingData as _CleanMissingData
from mmlspark_tpu.featurize.clean import CleanMissingDataModel as _CleanMissingDataModel
from mmlspark_tpu.featurize.convert import DataConversion as _DataConversion
from mmlspark_tpu.featurize.featurize import Featurize as _Featurize
from mmlspark_tpu.featurize.featurize import FeaturizeModel as _FeaturizeModel
from mmlspark_tpu.featurize.indexer import IndexToValue as _IndexToValue
from mmlspark_tpu.featurize.indexer import ValueIndexer as _ValueIndexer
from mmlspark_tpu.featurize.indexer import ValueIndexerModel as _ValueIndexerModel
from mmlspark_tpu.featurize.text import TextFeaturizer as _TextFeaturizer
from mmlspark_tpu.featurize.text import TextFeaturizerModel as _TextFeaturizerModel
from mmlspark_tpu.io.http.http_transformer import HTTPTransformer as _HTTPTransformer
from mmlspark_tpu.io.http.http_transformer import JSONInputParser as _JSONInputParser
from mmlspark_tpu.io.http.http_transformer import JSONOutputParser as _JSONOutputParser
from mmlspark_tpu.io.http.http_transformer import SimpleHTTPTransformer as _SimpleHTTPTransformer
from mmlspark_tpu.models.cntk_model import CNTKModel as _CNTKModel
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer as _ImageFeaturizer
from mmlspark_tpu.models.isolation_forest import IsolationForest as _IsolationForest
from mmlspark_tpu.models.isolation_forest import IsolationForestModel as _IsolationForestModel
from mmlspark_tpu.models.knn import ConditionalKNN as _ConditionalKNN
from mmlspark_tpu.models.knn import ConditionalKNNModel as _ConditionalKNNModel
from mmlspark_tpu.models.knn import KNN as _KNN
from mmlspark_tpu.models.knn import KNNModel as _KNNModel
from mmlspark_tpu.models.lightgbm import LightGBMClassificationModel as _LightGBMClassificationModel
from mmlspark_tpu.models.lightgbm import LightGBMClassifier as _LightGBMClassifier
from mmlspark_tpu.models.lightgbm import LightGBMRanker as _LightGBMRanker
from mmlspark_tpu.models.lightgbm import LightGBMRankerModel as _LightGBMRankerModel
from mmlspark_tpu.models.lightgbm import LightGBMRegressionModel as _LightGBMRegressionModel
from mmlspark_tpu.models.lightgbm import LightGBMRegressor as _LightGBMRegressor
from mmlspark_tpu.models.onnx_model import ONNXModel as _ONNXModel
from mmlspark_tpu.models.sar import RankingAdapter as _RankingAdapter
from mmlspark_tpu.models.sar import RankingAdapterModel as _RankingAdapterModel
from mmlspark_tpu.models.sar import RankingEvaluator as _RankingEvaluator
from mmlspark_tpu.models.sar import RankingTrainValidationSplit as _RankingTrainValidationSplit
from mmlspark_tpu.models.sar import RankingTrainValidationSplitModel as _RankingTrainValidationSplitModel
from mmlspark_tpu.models.sar import RecommendationIndexer as _RecommendationIndexer
from mmlspark_tpu.models.sar import RecommendationIndexerModel as _RecommendationIndexerModel
from mmlspark_tpu.models.sar import SAR as _SAR
from mmlspark_tpu.models.sar import SARModel as _SARModel
from mmlspark_tpu.models.vw import VowpalWabbitClassificationModel as _VowpalWabbitClassificationModel
from mmlspark_tpu.models.vw import VowpalWabbitClassifier as _VowpalWabbitClassifier
from mmlspark_tpu.models.vw import VowpalWabbitFeaturizer as _VowpalWabbitFeaturizer
from mmlspark_tpu.models.vw import VowpalWabbitInteractions as _VowpalWabbitInteractions
from mmlspark_tpu.models.vw import VowpalWabbitRegressionModel as _VowpalWabbitRegressionModel
from mmlspark_tpu.models.vw import VowpalWabbitRegressor as _VowpalWabbitRegressor
from mmlspark_tpu.ops.image_ops import ImageSetAugmenter as _ImageSetAugmenter
from mmlspark_tpu.ops.image_ops import ImageTransformer as _ImageTransformer
from mmlspark_tpu.ops.image_ops import UnrollBinaryImage as _UnrollBinaryImage
from mmlspark_tpu.ops.image_ops import UnrollImage as _UnrollImage
from mmlspark_tpu.stages.basic import Cacher as _Cacher
from mmlspark_tpu.stages.basic import ClassBalancer as _ClassBalancer
from mmlspark_tpu.stages.basic import ClassBalancerModel as _ClassBalancerModel
from mmlspark_tpu.stages.basic import DropColumns as _DropColumns
from mmlspark_tpu.stages.basic import EnsembleByKey as _EnsembleByKey
from mmlspark_tpu.stages.basic import Explode as _Explode
from mmlspark_tpu.stages.basic import Lambda as _Lambda
from mmlspark_tpu.stages.basic import MultiColumnAdapter as _MultiColumnAdapter
from mmlspark_tpu.stages.basic import PartitionConsolidator as _PartitionConsolidator
from mmlspark_tpu.stages.basic import RenameColumn as _RenameColumn
from mmlspark_tpu.stages.basic import Repartition as _Repartition
from mmlspark_tpu.stages.basic import SelectColumns as _SelectColumns
from mmlspark_tpu.stages.basic import StratifiedRepartition as _StratifiedRepartition
from mmlspark_tpu.stages.basic import SummarizeData as _SummarizeData
from mmlspark_tpu.stages.basic import TextPreprocessor as _TextPreprocessor
from mmlspark_tpu.stages.basic import Timer as _Timer
from mmlspark_tpu.stages.basic import UDFTransformer as _UDFTransformer
from mmlspark_tpu.stages.minibatch import DynamicMiniBatchTransformer as _DynamicMiniBatchTransformer
from mmlspark_tpu.stages.minibatch import FixedMiniBatchTransformer as _FixedMiniBatchTransformer
from mmlspark_tpu.stages.minibatch import FlattenBatch as _FlattenBatch
from mmlspark_tpu.stages.minibatch import TimeIntervalMiniBatchTransformer as _TimeIntervalMiniBatchTransformer
from mmlspark_tpu.train.compute_statistics import ComputeModelStatistics as _ComputeModelStatistics
from mmlspark_tpu.train.compute_statistics import ComputePerInstanceStatistics as _ComputePerInstanceStatistics
from mmlspark_tpu.train.train_classifier import TrainClassifier as _TrainClassifier
from mmlspark_tpu.train.train_classifier import TrainRegressor as _TrainRegressor
from mmlspark_tpu.train.train_classifier import TrainedClassifierModel as _TrainedClassifierModel
from mmlspark_tpu.train.train_classifier import TrainedRegressorModel as _TrainedRegressorModel


class BestModel(_BestModel):
    """Generated wrapper over :class:`mmlspark_tpu.automl.search.BestModel`.

    Params:
      allScores: Per-candidate scores
      bestModel: Winning fitted model
      bestScore: Winning metric value
    """

    def __init__(self, *, allScores=None, bestModel=None, bestScore=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class FindBestModel(_FindBestModel):
    """Generated wrapper over :class:`mmlspark_tpu.automl.search.FindBestModel`.

    Params:
      evaluationMetric: Metric name
      labelCol: Label column
      models: Candidate estimators
    """

    def __init__(self, *, evaluationMetric='accuracy', labelCol='label', models=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TuneHyperparameters(_TuneHyperparameters):
    """Generated wrapper over :class:`mmlspark_tpu.automl.search.TuneHyperparameters`.

    Params:
      estimator: Base estimator
      evaluationMetric: Metric name
      labelCol: Label column
      numFolds: CV folds
      numRuns: Candidates to sample (random search)
      parallelism: Concurrent candidate fits
      randomSearch: Random (true) vs grid (false)
      searchSpace: Built hyperparam space
      seed: Sampling seed
    """

    def __init__(self, *, estimator=None, evaluationMetric='accuracy', labelCol='label', numFolds=3, numRuns=10, parallelism=4, randomSearch=True, searchSpace=None, seed=0):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TuneHyperparametersModel(_TuneHyperparametersModel):
    """Generated wrapper over :class:`mmlspark_tpu.automl.search.TuneHyperparametersModel`.

    Params:
      allScores: Per-candidate CV scores
      bestMetric: Winning CV metric
      bestModel: Winning refit model
      bestParams: Winning param map
    """

    def __init__(self, *, allScores=None, bestMetric=None, bestModel=None, bestParams=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class BingImageSearch(_BingImageSearch):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.anomaly.BingImageSearch`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      count: Results per query
      errorCol: Column receiving per-row errors
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      q: Search query (value or column)
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, count={'value': 10}, errorCol='', location='westus', outputCol=_UNSET, q=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DetectEntireSeries(_DetectEntireSeries):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.anomaly.DetectEntireSeries`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      granularity: Series granularity
      location: Service region, e.g. eastus
      maxAnomalyRatio: Max fraction of anomalies
      outputCol: The name of the output column
      sensitivity: Detection sensitivity 0-99
      series: Timeseries: list of {timestamp, value} points per row
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', granularity={'value': 'daily'}, location='westus', maxAnomalyRatio=_UNSET, outputCol=_UNSET, sensitivity=_UNSET, series=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DetectLastAnomaly(_DetectLastAnomaly):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.anomaly.DetectLastAnomaly`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      granularity: Series granularity
      location: Service region, e.g. eastus
      maxAnomalyRatio: Max fraction of anomalies
      outputCol: The name of the output column
      sensitivity: Detection sensitivity 0-99
      series: Timeseries: list of {timestamp, value} points per row
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', granularity={'value': 'daily'}, location='westus', maxAnomalyRatio=_UNSET, outputCol=_UNSET, sensitivity=_UNSET, series=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class FindSimilarFace(_FindSimilarFace):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.face.FindSimilarFace`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      faceId: Query face ID
      faceIds: Candidate face IDs (list or csv)
      faceListId: Face list to search
      largeFaceListId: Large face list to search
      location: Service region, e.g. eastus
      maxNumOfCandidatesReturned: Max matches returned
      mode: matchPerson | matchFace
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', faceId=_UNSET, faceIds=_UNSET, faceListId=_UNSET, largeFaceListId=_UNSET, location='westus', maxNumOfCandidatesReturned={'value': 20}, mode={'value': 'matchPerson'}, outputCol=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class GroupFaces(_GroupFaces):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.face.GroupFaces`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      faceIds: Face IDs to group (list or csv)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', faceIds=_UNSET, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class IdentifyFaces(_IdentifyFaces):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.face.IdentifyFaces`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      confidenceThreshold: Identification confidence threshold
      errorCol: Column receiving per-row errors
      faceIds: Face IDs to identify (list or csv)
      largePersonGroupId: Target large person group (excludes personGroupId)
      location: Service region, e.g. eastus
      maxNumOfCandidatesReturned: Candidates per face
      outputCol: The name of the output column
      personGroupId: Target person group
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, confidenceThreshold=_UNSET, errorCol='', faceIds=_UNSET, largePersonGroupId=_UNSET, location='westus', maxNumOfCandidatesReturned={'value': 1}, outputCol=_UNSET, personGroupId=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VerifyFaces(_VerifyFaces):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.face.VerifyFaces`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      faceId: Face ID (face-to-person mode)
      faceId1: First face ID (face-to-face mode)
      faceId2: Second face ID (face-to-face mode)
      largePersonGroupId: Large person group (face-to-person)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      personGroupId: Person group (face-to-person)
      personId: Person ID (face-to-person)
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', faceId=_UNSET, faceId1=_UNSET, faceId2=_UNSET, largePersonGroupId=_UNSET, location='westus', outputCol=_UNSET, personGroupId=_UNSET, personId=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SpeechToText(_SpeechToText):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.speech.SpeechToText`.

    Params:
      audioData: Raw audio bytes (value or column)
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      format: simple | detailed output
      language: Recognition language
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      profanity: masked | removed | raw
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, audioData=_UNSET, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', format={'value': 'simple'}, language={'value': 'en-US'}, location='westus', outputCol=_UNSET, profanity={'value': 'masked'}, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class EntityDetector(_EntityDetector):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.text.EntityDetector`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      language: Document language
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      text: Input text (value or column)
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', language={'value': 'en'}, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, text=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class KeyPhraseExtractor(_KeyPhraseExtractor):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.text.KeyPhraseExtractor`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      language: Document language
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      text: Input text (value or column)
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', language={'value': 'en'}, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, text=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LanguageDetector(_LanguageDetector):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.text.LanguageDetector`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      language: Document language
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      text: Input text (value or column)
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', language={'value': 'en'}, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, text=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class NER(_NER):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.text.NER`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      language: Document language
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      text: Input text (value or column)
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', language={'value': 'en'}, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, text=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TextSentiment(_TextSentiment):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.text.TextSentiment`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      language: Document language
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      text: Input text (value or column)
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', language={'value': 'en'}, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, text=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Translate(_Translate):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.text.Translate`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      fromLanguage: Source language (optional)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      text: Text to translate
      toLanguage: Target language(s), comma-joined
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', fromLanguage=_UNSET, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, text=_UNSET, toLanguage=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class AnalyzeImage(_AnalyzeImage):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.vision.AnalyzeImage`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      imageBytes: Raw image bytes (value or column)
      imageUrl: Image URL (value or column)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
      visualFeatures: Comma-joined features (Categories,Tags,Description,...)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', imageBytes=_UNSET, imageUrl=_UNSET, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, url='', visualFeatures=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DescribeImage(_DescribeImage):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.vision.DescribeImage`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      imageBytes: Raw image bytes (value or column)
      imageUrl: Image URL (value or column)
      location: Service region, e.g. eastus
      maxCandidates: Caption candidates
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', imageBytes=_UNSET, imageUrl=_UNSET, location='westus', maxCandidates={'value': 1}, outputCol=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DetectFace(_DetectFace):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.vision.DetectFace`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      imageBytes: Raw image bytes (value or column)
      imageUrl: Image URL (value or column)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      returnFaceAttributes: Comma-joined face attributes to return
      returnFaceLandmarks: Return the 27-point landmarks
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', imageBytes=_UNSET, imageUrl=_UNSET, location='westus', outputCol=_UNSET, returnFaceAttributes=_UNSET, returnFaceLandmarks={'value': False}, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class OCR(_OCR):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.vision.OCR`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      detectOrientation: Detect text orientation
      errorCol: Column receiving per-row errors
      imageBytes: Raw image bytes (value or column)
      imageUrl: Image URL (value or column)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, detectOrientation={'value': True}, errorCol='', imageBytes=_UNSET, imageUrl=_UNSET, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TagImage(_TagImage):
    """Generated wrapper over :class:`mmlspark_tpu.cognitive.vision.TagImage`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Column receiving per-row errors
      imageBytes: Raw image bytes (value or column)
      imageUrl: Image URL (value or column)
      location: Service region, e.g. eastus
      outputCol: The name of the output column
      subscriptionKey: API key sent as Ocp-Apim-Subscription-Key
      url: Full service URL (overrides location routing)
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, errorCol='', imageBytes=_UNSET, imageUrl=_UNSET, location='westus', outputCol=_UNSET, subscriptionKey=_UNSET, url=''):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Pipeline(_Pipeline):
    """Generated wrapper over :class:`mmlspark_tpu.core.pipeline.Pipeline`.

    Params:
      stages: The stages of the pipeline
    """

    def __init__(self, *, stages=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class PipelineModel(_PipelineModel):
    """Generated wrapper over :class:`mmlspark_tpu.core.pipeline.PipelineModel`.

    Params:
      stages: The fitted stages
    """

    def __init__(self, *, stages=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ImageLIME(_ImageLIME):
    """Generated wrapper over :class:`mmlspark_tpu.explain.lime.ImageLIME`.

    Params:
      cellSize: Superpixel size
      inputCol: Column to perturb
      kernelWidth: Proximity kernel width
      model: Inner model to explain
      modifier: SLIC spatial weight
      nSamples: Perturbations per instance
      outputCol: Explanation weights column
      predictionCol: Inner model's output column
      regularization: Lasso lambda
      samplingFraction: P(keep superpixel)
      seed: Sampling seed
      superpixelCol: Output superpixel column
    """

    def __init__(self, *, cellSize=16, inputCol=_UNSET, kernelWidth=0.75, model=None, modifier=130.0, nSamples=512, outputCol='weights', predictionCol='prediction', regularization=0.0, samplingFraction=0.7, seed=0, superpixelCol='superpixels'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TabularLIME(_TabularLIME):
    """Generated wrapper over :class:`mmlspark_tpu.explain.lime.TabularLIME`.

    Params:
      inputCol: Column to perturb
      kernelWidth: Proximity kernel width
      model: Inner model to explain
      nSamples: Perturbations per instance
      outputCol: Explanation weights column
      predictionCol: Inner model's output column
      regularization: Lasso lambda
      seed: Sampling seed
    """

    def __init__(self, *, inputCol=_UNSET, kernelWidth=0.75, model=None, nSamples=512, outputCol='weights', predictionCol='prediction', regularization=0.0, seed=0):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TabularLIMEModel(_TabularLIMEModel):
    """Generated wrapper over :class:`mmlspark_tpu.explain.lime.TabularLIMEModel`.

    Params:
      featureMeans: Column means
      featureStds: Column stds
      inputCol: Column to perturb
      kernelWidth: Proximity kernel width
      model: Inner model to explain
      nSamples: Perturbations per instance
      outputCol: Explanation weights column
      predictionCol: Inner model's output column
      regularization: Lasso lambda
      seed: Sampling seed
    """

    def __init__(self, *, featureMeans=None, featureStds=None, inputCol=_UNSET, kernelWidth=0.75, model=None, nSamples=512, outputCol='weights', predictionCol='prediction', regularization=0.0, seed=0):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SuperpixelTransformer(_SuperpixelTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.explain.superpixel.SuperpixelTransformer`.

    Params:
      cellSize: Approx superpixel size in px
      inputCol: Image column
      modifier: Spatial-vs-color weight
      outputCol: Superpixel column
    """

    def __init__(self, *, cellSize=16, inputCol='image', modifier=130.0, outputCol='superpixels'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class CleanMissingData(_CleanMissingData):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.clean.CleanMissingData`.

    Params:
      cleaningMode: Mean|Median|Custom
      customValue: Fill value for Custom mode
      inputCols: Columns to impute
      outputCols: Output columns
    """

    def __init__(self, *, cleaningMode='Mean', customValue=None, inputCols=None, outputCols=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class CleanMissingDataModel(_CleanMissingDataModel):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.clean.CleanMissingDataModel`.

    Params:
      cleaningMode: Mean|Median|Custom
      customValue: Fill value for Custom mode
      fillValues: column -> fill value
      inputCols: Columns to impute
      outputCols: Output columns
    """

    def __init__(self, *, cleaningMode='Mean', customValue=None, fillValues=None, inputCols=None, outputCols=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DataConversion(_DataConversion):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.convert.DataConversion`.

    Params:
      cols: Columns to convert
      convertTo: Target type
      dateTimeFormat: Format for date conversion
    """

    def __init__(self, *, cols=None, convertTo='double', dateTimeFormat='yyyy-MM-dd HH:mm:ss'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Featurize(_Featurize):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.featurize.Featurize`.

    Params:
      imputeMissing: Mean-impute numeric NaNs
      inputCols: Columns to featurize (default: all but output)
      numFeatures: Hash buckets for free-text columns
      oneHotEncodeCategoricals: One-hot instead of index-encode
      outputCol: Assembled vector column
    """

    def __init__(self, *, imputeMissing=True, inputCols=None, numFeatures=262144, oneHotEncodeCategoricals=True, outputCol='features'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class FeaturizeModel(_FeaturizeModel):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.featurize.FeaturizeModel`.

    Params:
      imputeMissing: Mean-impute numeric NaNs
      inputCols: Columns to featurize (default: all but output)
      numFeatures: Hash buckets for free-text columns
      oneHotEncodeCategoricals: One-hot instead of index-encode
      outputCol: Assembled vector column
      plan: Per-column featurization plan
    """

    def __init__(self, *, imputeMissing=True, inputCols=None, numFeatures=262144, oneHotEncodeCategoricals=True, outputCol='features', plan=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class IndexToValue(_IndexToValue):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.indexer.IndexToValue`.

    Params:
      inputCol: The name of the input column
      outputCol: The name of the output column
    """

    def __init__(self, *, inputCol=_UNSET, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ValueIndexer(_ValueIndexer):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.indexer.ValueIndexer`.

    Params:
      inputCol: The name of the input column
      outputCol: The name of the output column
    """

    def __init__(self, *, inputCol=_UNSET, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ValueIndexerModel(_ValueIndexerModel):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.indexer.ValueIndexerModel`.

    Params:
      inputCol: The name of the input column
      levels: Ordered distinct levels
      outputCol: The name of the output column
    """

    def __init__(self, *, inputCol=_UNSET, levels=None, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TextFeaturizer(_TextFeaturizer):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.text.TextFeaturizer`.

    Params:
      binary: Binary term counts
      inputCol: Text column
      minDocFreq: Min docs for a term to count
      nGramLength: n-gram length
      numFeatures: Hash buckets
      outputCol: Output vector column
      stopWords: Stop word list
      toLowercase: Lowercase before tokenizing
      tokenizerPattern: Token split regex
      useIDF: Rescale with inverse document frequency
      useNGram: Add n-grams
      useStopWordsRemover: Drop stop words
      useTokenizer: Regex-tokenize the text
    """

    def __init__(self, *, binary=False, inputCol=_UNSET, minDocFreq=1, nGramLength=2, numFeatures=4096, outputCol='features', stopWords=None, toLowercase=True, tokenizerPattern='\\s+', useIDF=True, useNGram=False, useStopWordsRemover=False, useTokenizer=True):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TextFeaturizerModel(_TextFeaturizerModel):
    """Generated wrapper over :class:`mmlspark_tpu.featurize.text.TextFeaturizerModel`.

    Params:
      binary: Binary term counts
      idfVector: Fitted IDF weights
      inputCol: Text column
      minDocFreq: Min docs for a term to count
      nGramLength: n-gram length
      numFeatures: Hash buckets
      outputCol: Output vector column
      stopWords: Stop word list
      toLowercase: Lowercase before tokenizing
      tokenizerPattern: Token split regex
      useIDF: Rescale with inverse document frequency
      useNGram: Add n-grams
      useStopWordsRemover: Drop stop words
      useTokenizer: Regex-tokenize the text
    """

    def __init__(self, *, binary=False, idfVector=None, inputCol=_UNSET, minDocFreq=1, nGramLength=2, numFeatures=4096, outputCol='features', stopWords=None, toLowercase=True, tokenizerPattern='\\s+', useIDF=True, useNGram=False, useStopWordsRemover=False, useTokenizer=True):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class HTTPTransformer(_HTTPTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.io.http.http_transformer.HTTPTransformer`.

    Params:
      backoffs: Retry backoffs in ms
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      inputCol: The name of the input column
      outputCol: The name of the output column
    """

    def __init__(self, *, backoffs=[100, 500, 1000], concurrency=4, concurrentTimeout=60.0, inputCol=_UNSET, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class JSONInputParser(_JSONInputParser):
    """Generated wrapper over :class:`mmlspark_tpu.io.http.http_transformer.JSONInputParser`.

    Params:
      headers: Extra headers
      inputCol: The name of the input column
      method: HTTP method
      outputCol: The name of the output column
      url: Target URL
    """

    def __init__(self, *, headers=None, inputCol=_UNSET, method='POST', outputCol=_UNSET, url=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class JSONOutputParser(_JSONOutputParser):
    """Generated wrapper over :class:`mmlspark_tpu.io.http.http_transformer.JSONOutputParser`.

    Params:
      inputCol: The name of the input column
      outputCol: The name of the output column
    """

    def __init__(self, *, inputCol=_UNSET, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SimpleHTTPTransformer(_SimpleHTTPTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.io.http.http_transformer.SimpleHTTPTransformer`.

    Params:
      concurrency: In-flight requests
      concurrentTimeout: Per-request timeout (s)
      errorCol: Error output column
      flattenOutputBatches: unused (API parity)
      headers: Extra headers
      inputCol: The name of the input column
      method: HTTP method
      outputCol: The name of the output column
      url: Target URL
    """

    def __init__(self, *, concurrency=4, concurrentTimeout=60.0, errorCol='errors', flattenOutputBatches=False, headers=None, inputCol=_UNSET, method='POST', outputCol=_UNSET, url=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class CNTKModel(_CNTKModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.cntk_model.CNTKModel`.

    Params:
      batchInput: Batch rows before evaluation
      inputCol: Input column of feature vectors
      inputNode: Graph input: index (int) or name (str)
      miniBatchSize: Rows per inference minibatch
      modelPayload: Serialized ONNX model bytes
      outputCol: Output column
      outputNode: Graph output: index (int) or name (str)
    """

    def __init__(self, *, batchInput=True, inputCol='features', inputNode=0, miniBatchSize=64, modelPayload=_UNSET, outputCol='output', outputNode=0):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ImageFeaturizer(_ImageFeaturizer):
    """Generated wrapper over :class:`mmlspark_tpu.models.image_featurizer.ImageFeaturizer`.

    Params:
      centerCropAfterResize: Center-crop to the target size
      channelNormalizationMeans: Per-channel means
      channelNormalizationStds: Per-channel stds
      colorScaleFactor: Pixel pre-scale
      cutOutputLayers: How many output heads to cut: 0 = final output, k = k-th output from the end (featurization taps an earlier head)
      imageHeight: Model input height
      imageWidth: Model input width
      inputCol: Image column
      miniBatchSize: Rows per inference minibatch
      modelPayload: Serialized ONNX model bytes
      outputCol: Feature vector column
    """

    def __init__(self, *, centerCropAfterResize=False, channelNormalizationMeans=None, channelNormalizationStds=None, colorScaleFactor=1.0, cutOutputLayers=1, imageHeight=224, imageWidth=224, inputCol='image', miniBatchSize=64, modelPayload=_UNSET, outputCol='features'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class IsolationForest(_IsolationForest):
    """Generated wrapper over :class:`mmlspark_tpu.models.isolation_forest.IsolationForest`.

    Params:
      contamination: Expected outlier fraction
      featuresCol: Feature vector column
      maxFeatures: unused (API parity)
      maxSamples: Subsample per tree
      numEstimators: Trees in the forest
      predictionCol: 0/1 outlier column
      randomSeed: RNG seed
      scoreCol: Anomaly score column
    """

    def __init__(self, *, contamination=0.1, featuresCol='features', maxFeatures=1.0, maxSamples=256, numEstimators=100, predictionCol='predictedLabel', randomSeed=1, scoreCol='outlierScore'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class IsolationForestModel(_IsolationForestModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.isolation_forest.IsolationForestModel`.

    Params:
      contamination: Expected outlier fraction
      featuresCol: Feature vector column
      maxFeatures: unused (API parity)
      maxSamples: Subsample per tree
      numEstimators: Trees in the forest
      predictionCol: 0/1 outlier column
      randomSeed: RNG seed
      scoreCol: Anomaly score column
      subsampleSize: psi used at fit time
      threshold: Outlier score threshold
      trees: Isolation trees
    """

    def __init__(self, *, contamination=0.1, featuresCol='features', maxFeatures=1.0, maxSamples=256, numEstimators=100, predictionCol='predictedLabel', randomSeed=1, scoreCol='outlierScore', subsampleSize=256, threshold=0.5, trees=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ConditionalKNN(_ConditionalKNN):
    """Generated wrapper over :class:`mmlspark_tpu.models.knn.ConditionalKNN`.

    Params:
      conditionerCol: Query-side set of allowed labels
      featuresCol: Feature vector column
      k: Neighbors to return
      labelCol: Index-side condition label column
      leafSize: unused (ball-tree API parity)
      outputCol: Matches column
      valuesCol: Payload column returned with matches
    """

    def __init__(self, *, conditionerCol='conditioner', featuresCol='features', k=5, labelCol='labels', leafSize=50, outputCol='output', valuesCol='values'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ConditionalKNNModel(_ConditionalKNNModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.knn.ConditionalKNNModel`.

    Params:
      conditionerCol: Query-side set of allowed labels
      featuresCol: Feature vector column
      indexFeatures: Indexed feature matrix
      indexLabels: Index-side labels
      indexValues: Indexed payloads
      k: Neighbors to return
      labelCol: Index-side condition label column
      leafSize: unused (ball-tree API parity)
      outputCol: Matches column
      valuesCol: Payload column returned with matches
    """

    def __init__(self, *, conditionerCol='conditioner', featuresCol='features', indexFeatures=None, indexLabels=None, indexValues=None, k=5, labelCol='labels', leafSize=50, outputCol='output', valuesCol='values'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class KNN(_KNN):
    """Generated wrapper over :class:`mmlspark_tpu.models.knn.KNN`.

    Params:
      featuresCol: Feature vector column
      k: Neighbors to return
      leafSize: unused (ball-tree API parity)
      outputCol: Matches column
      valuesCol: Payload column returned with matches
    """

    def __init__(self, *, featuresCol='features', k=5, leafSize=50, outputCol='output', valuesCol='values'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class KNNModel(_KNNModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.knn.KNNModel`.

    Params:
      featuresCol: Feature vector column
      indexFeatures: Indexed feature matrix
      indexValues: Indexed payloads
      k: Neighbors to return
      leafSize: unused (ball-tree API parity)
      outputCol: Matches column
      valuesCol: Payload column returned with matches
    """

    def __init__(self, *, featuresCol='features', indexFeatures=None, indexValues=None, k=5, leafSize=50, outputCol='output', valuesCol='values'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LightGBMClassificationModel(_LightGBMClassificationModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.lightgbm.LightGBMClassificationModel`.

    Params:
      baggingFraction: Row subsample fraction
      baggingFreq: Resample bag every k iterations (0 = off)
      baggingSeed: Bagging random seed
      boostFromAverage: Seed scores at the label average
      booster: The trained booster
      boostingType: gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
      categoricalSlotIndexes: Categorical feature indices
      categoricalSlotNames: Categorical feature names
      defaultListenPort: Legacy socket-allreduce base port (no-op on TPU)
      deviceType: Compute placement: tpu|cpu|gpu
      driverListenPort: Legacy driver rendezvous port (no-op on TPU)
      earlyStoppingRound: Early stopping patience (0 = off)
      featureFraction: Feature subsample fraction
      featuresCol: The name of the features column
      growPolicy: lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
      histMerge: Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
      histQuantize: Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
      initScoreCol: Initial (margin) score column
      isProvideTrainingMetric: Record metrics on training data too
      isUnbalance: Reweight unbalanced binary labels
      labelCol: The name of the label column
      lambdaL1: L1 regularization
      lambdaL2: L2 regularization
      leafPredictionCol: Output column of leaf indices
      learningRate: Shrinkage rate
      matrixType: auto|dense|sparse host matrix handling
      maxBin: Max feature bins
      maxDepth: Max tree depth (-1 = unlimited)
      metric: Eval metric ('' = objective default)
      minDataInLeaf: Min rows per leaf
      minSumHessianInLeaf: Min leaf hessian sum
      modelString: Warm-start model string
      numBatches: Split training into sequential batches (continuation-trained)
      numGradQuantBins: LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
      numIterations: Number of boosting iterations
      numLeaves: Max leaves per tree
      numTasks: Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
      numThreads: Host-side threads for binning (0 = default)
      objective: Training objective
      parallelism: Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
      predictBackend: Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
      predictionCol: The name of the prediction column
      probabilityCol: Class probability output column
      quantTrainRenewLeaf: LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
      rawPredictionCol: Raw margin output column
      seed: Master random seed
      slotNames: Feature vector slot names
      splitBatch: k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
      stochasticRounding: LightGBM's stochastic_rounding: False rounds gradients to the nearest level
      thresholds: Per-class prediction thresholds
      timeout: Distributed initialization timeout in seconds
      topK: Top-k features voted per worker in voting_parallel
      useBarrierExecutionMode: Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
      useQuantizedGrad: LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
      validationIndicatorCol: Boolean column marking validation rows
      verbosity: Native verbosity
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, baggingFraction=1.0, baggingFreq=0, baggingSeed=3, boostFromAverage=True, booster=_UNSET, boostingType='gbdt', categoricalSlotIndexes=None, categoricalSlotNames=None, defaultListenPort=12400, deviceType='tpu', driverListenPort=0, earlyStoppingRound=0, featureFraction=1.0, featuresCol='features', growPolicy='lossguide', histMerge='auto', histQuantize='off', initScoreCol=_UNSET, isProvideTrainingMetric=False, isUnbalance=False, labelCol='label', lambdaL1=0.0, lambdaL2=0.0, leafPredictionCol='', learningRate=0.1, matrixType='auto', maxBin=255, maxDepth=-1, metric='', minDataInLeaf=20, minSumHessianInLeaf=0.001, modelString='', numBatches=0, numGradQuantBins=0, numIterations=100, numLeaves=31, numTasks=0, numThreads=0, objective='regression', parallelism='data_parallel', predictBackend='auto', predictionCol='prediction', probabilityCol='probability', quantTrainRenewLeaf=True, rawPredictionCol='rawPrediction', seed=0, slotNames=None, splitBatch=0, stochasticRounding=True, thresholds=None, timeout=1200.0, topK=20, useBarrierExecutionMode=False, useQuantizedGrad=False, validationIndicatorCol=_UNSET, verbosity=1, weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LightGBMClassifier(_LightGBMClassifier):
    """Generated wrapper over :class:`mmlspark_tpu.models.lightgbm.LightGBMClassifier`.

    Params:
      baggingFraction: Row subsample fraction
      baggingFreq: Resample bag every k iterations (0 = off)
      baggingSeed: Bagging random seed
      boostFromAverage: Seed scores at the label average
      boostingType: gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
      categoricalSlotIndexes: Categorical feature indices
      categoricalSlotNames: Categorical feature names
      defaultListenPort: Legacy socket-allreduce base port (no-op on TPU)
      deviceType: Compute placement: tpu|cpu|gpu
      driverListenPort: Legacy driver rendezvous port (no-op on TPU)
      earlyStoppingRound: Early stopping patience (0 = off)
      featureFraction: Feature subsample fraction
      featuresCol: The name of the features column
      growPolicy: lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
      histMerge: Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
      histQuantize: Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
      initScoreCol: Initial (margin) score column
      isProvideTrainingMetric: Record metrics on training data too
      isUnbalance: Reweight unbalanced binary labels
      labelCol: The name of the label column
      lambdaL1: L1 regularization
      lambdaL2: L2 regularization
      leafPredictionCol: Output column of leaf indices
      learningRate: Shrinkage rate
      matrixType: auto|dense|sparse host matrix handling
      maxBin: Max feature bins
      maxDepth: Max tree depth (-1 = unlimited)
      metric: Eval metric ('' = objective default)
      minDataInLeaf: Min rows per leaf
      minSumHessianInLeaf: Min leaf hessian sum
      modelString: Warm-start model string
      numBatches: Split training into sequential batches (continuation-trained)
      numGradQuantBins: LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
      numIterations: Number of boosting iterations
      numLeaves: Max leaves per tree
      numTasks: Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
      numThreads: Host-side threads for binning (0 = default)
      objective: Training objective
      parallelism: Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
      predictBackend: Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
      predictionCol: The name of the prediction column
      probabilityCol: Class probability output column
      quantTrainRenewLeaf: LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
      rawPredictionCol: Raw margin output column
      seed: Master random seed
      slotNames: Feature vector slot names
      splitBatch: k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
      stochasticRounding: LightGBM's stochastic_rounding: False rounds gradients to the nearest level
      thresholds: Per-class prediction thresholds
      timeout: Distributed initialization timeout in seconds
      topK: Top-k features voted per worker in voting_parallel
      useBarrierExecutionMode: Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
      useQuantizedGrad: LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
      validationIndicatorCol: Boolean column marking validation rows
      verbosity: Native verbosity
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, baggingFraction=1.0, baggingFreq=0, baggingSeed=3, boostFromAverage=True, boostingType='gbdt', categoricalSlotIndexes=None, categoricalSlotNames=None, defaultListenPort=12400, deviceType='tpu', driverListenPort=0, earlyStoppingRound=0, featureFraction=1.0, featuresCol='features', growPolicy='lossguide', histMerge='auto', histQuantize='off', initScoreCol=_UNSET, isProvideTrainingMetric=False, isUnbalance=False, labelCol='label', lambdaL1=0.0, lambdaL2=0.0, leafPredictionCol='', learningRate=0.1, matrixType='auto', maxBin=255, maxDepth=-1, metric='', minDataInLeaf=20, minSumHessianInLeaf=0.001, modelString='', numBatches=0, numGradQuantBins=0, numIterations=100, numLeaves=31, numTasks=0, numThreads=0, objective='binary', parallelism='data_parallel', predictBackend='auto', predictionCol='prediction', probabilityCol='probability', quantTrainRenewLeaf=True, rawPredictionCol='rawPrediction', seed=0, slotNames=None, splitBatch=0, stochasticRounding=True, thresholds=None, timeout=1200.0, topK=20, useBarrierExecutionMode=False, useQuantizedGrad=False, validationIndicatorCol=_UNSET, verbosity=1, weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LightGBMRanker(_LightGBMRanker):
    """Generated wrapper over :class:`mmlspark_tpu.models.lightgbm.LightGBMRanker`.

    Params:
      baggingFraction: Row subsample fraction
      baggingFreq: Resample bag every k iterations (0 = off)
      baggingSeed: Bagging random seed
      boostFromAverage: Seed scores at the label average
      boostingType: gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
      categoricalSlotIndexes: Categorical feature indices
      categoricalSlotNames: Categorical feature names
      defaultListenPort: Legacy socket-allreduce base port (no-op on TPU)
      deviceType: Compute placement: tpu|cpu|gpu
      driverListenPort: Legacy driver rendezvous port (no-op on TPU)
      earlyStoppingRound: Early stopping patience (0 = off)
      evalAt: NDCG eval positions
      featureFraction: Feature subsample fraction
      featuresCol: The name of the features column
      groupCol: Query group column
      growPolicy: lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
      histMerge: Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
      histQuantize: Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
      initScoreCol: Initial (margin) score column
      isProvideTrainingMetric: Record metrics on training data too
      isUnbalance: Reweight unbalanced binary labels
      labelCol: The name of the label column
      labelGain: Relevance gain per label value
      lambdaL1: L1 regularization
      lambdaL2: L2 regularization
      leafPredictionCol: Output column of leaf indices
      learningRate: Shrinkage rate
      matrixType: auto|dense|sparse host matrix handling
      maxBin: Max feature bins
      maxDepth: Max tree depth (-1 = unlimited)
      maxPosition: NDCG truncation for lambdarank
      metric: Eval metric ('' = objective default)
      minDataInLeaf: Min rows per leaf
      minSumHessianInLeaf: Min leaf hessian sum
      modelString: Warm-start model string
      numBatches: Split training into sequential batches (continuation-trained)
      numGradQuantBins: LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
      numIterations: Number of boosting iterations
      numLeaves: Max leaves per tree
      numTasks: Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
      numThreads: Host-side threads for binning (0 = default)
      objective: Training objective
      parallelism: Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
      predictBackend: Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
      predictionCol: The name of the prediction column
      quantTrainRenewLeaf: LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
      repartitionByGroupingColumn: Keep each query group within one worker shard
      seed: Master random seed
      slotNames: Feature vector slot names
      splitBatch: k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
      stochasticRounding: LightGBM's stochastic_rounding: False rounds gradients to the nearest level
      timeout: Distributed initialization timeout in seconds
      topK: Top-k features voted per worker in voting_parallel
      useBarrierExecutionMode: Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
      useQuantizedGrad: LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
      validationIndicatorCol: Boolean column marking validation rows
      verbosity: Native verbosity
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, baggingFraction=1.0, baggingFreq=0, baggingSeed=3, boostFromAverage=True, boostingType='gbdt', categoricalSlotIndexes=None, categoricalSlotNames=None, defaultListenPort=12400, deviceType='tpu', driverListenPort=0, earlyStoppingRound=0, evalAt=[1, 2, 3, 4, 5], featureFraction=1.0, featuresCol='features', groupCol='group', growPolicy='lossguide', histMerge='auto', histQuantize='off', initScoreCol=_UNSET, isProvideTrainingMetric=False, isUnbalance=False, labelCol='label', labelGain=None, lambdaL1=0.0, lambdaL2=0.0, leafPredictionCol='', learningRate=0.1, matrixType='auto', maxBin=255, maxDepth=-1, maxPosition=20, metric='', minDataInLeaf=20, minSumHessianInLeaf=0.001, modelString='', numBatches=0, numGradQuantBins=0, numIterations=100, numLeaves=31, numTasks=0, numThreads=0, objective='lambdarank', parallelism='data_parallel', predictBackend='auto', predictionCol='prediction', quantTrainRenewLeaf=True, repartitionByGroupingColumn=True, seed=0, slotNames=None, splitBatch=0, stochasticRounding=True, timeout=1200.0, topK=20, useBarrierExecutionMode=False, useQuantizedGrad=False, validationIndicatorCol=_UNSET, verbosity=1, weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LightGBMRankerModel(_LightGBMRankerModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.lightgbm.LightGBMRankerModel`.

    Params:
      baggingFraction: Row subsample fraction
      baggingFreq: Resample bag every k iterations (0 = off)
      baggingSeed: Bagging random seed
      boostFromAverage: Seed scores at the label average
      booster: The trained booster
      boostingType: gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
      categoricalSlotIndexes: Categorical feature indices
      categoricalSlotNames: Categorical feature names
      defaultListenPort: Legacy socket-allreduce base port (no-op on TPU)
      deviceType: Compute placement: tpu|cpu|gpu
      driverListenPort: Legacy driver rendezvous port (no-op on TPU)
      earlyStoppingRound: Early stopping patience (0 = off)
      featureFraction: Feature subsample fraction
      featuresCol: The name of the features column
      growPolicy: lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
      histMerge: Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
      histQuantize: Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
      initScoreCol: Initial (margin) score column
      isProvideTrainingMetric: Record metrics on training data too
      isUnbalance: Reweight unbalanced binary labels
      labelCol: The name of the label column
      lambdaL1: L1 regularization
      lambdaL2: L2 regularization
      leafPredictionCol: Output column of leaf indices
      learningRate: Shrinkage rate
      matrixType: auto|dense|sparse host matrix handling
      maxBin: Max feature bins
      maxDepth: Max tree depth (-1 = unlimited)
      metric: Eval metric ('' = objective default)
      minDataInLeaf: Min rows per leaf
      minSumHessianInLeaf: Min leaf hessian sum
      modelString: Warm-start model string
      numBatches: Split training into sequential batches (continuation-trained)
      numGradQuantBins: LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
      numIterations: Number of boosting iterations
      numLeaves: Max leaves per tree
      numTasks: Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
      numThreads: Host-side threads for binning (0 = default)
      objective: Training objective
      parallelism: Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
      predictBackend: Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
      predictionCol: The name of the prediction column
      quantTrainRenewLeaf: LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
      seed: Master random seed
      slotNames: Feature vector slot names
      splitBatch: k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
      stochasticRounding: LightGBM's stochastic_rounding: False rounds gradients to the nearest level
      timeout: Distributed initialization timeout in seconds
      topK: Top-k features voted per worker in voting_parallel
      useBarrierExecutionMode: Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
      useQuantizedGrad: LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
      validationIndicatorCol: Boolean column marking validation rows
      verbosity: Native verbosity
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, baggingFraction=1.0, baggingFreq=0, baggingSeed=3, boostFromAverage=True, booster=_UNSET, boostingType='gbdt', categoricalSlotIndexes=None, categoricalSlotNames=None, defaultListenPort=12400, deviceType='tpu', driverListenPort=0, earlyStoppingRound=0, featureFraction=1.0, featuresCol='features', growPolicy='lossguide', histMerge='auto', histQuantize='off', initScoreCol=_UNSET, isProvideTrainingMetric=False, isUnbalance=False, labelCol='label', lambdaL1=0.0, lambdaL2=0.0, leafPredictionCol='', learningRate=0.1, matrixType='auto', maxBin=255, maxDepth=-1, metric='', minDataInLeaf=20, minSumHessianInLeaf=0.001, modelString='', numBatches=0, numGradQuantBins=0, numIterations=100, numLeaves=31, numTasks=0, numThreads=0, objective='regression', parallelism='data_parallel', predictBackend='auto', predictionCol='prediction', quantTrainRenewLeaf=True, seed=0, slotNames=None, splitBatch=0, stochasticRounding=True, timeout=1200.0, topK=20, useBarrierExecutionMode=False, useQuantizedGrad=False, validationIndicatorCol=_UNSET, verbosity=1, weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LightGBMRegressionModel(_LightGBMRegressionModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.lightgbm.LightGBMRegressionModel`.

    Params:
      baggingFraction: Row subsample fraction
      baggingFreq: Resample bag every k iterations (0 = off)
      baggingSeed: Bagging random seed
      boostFromAverage: Seed scores at the label average
      booster: The trained booster
      boostingType: gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
      categoricalSlotIndexes: Categorical feature indices
      categoricalSlotNames: Categorical feature names
      defaultListenPort: Legacy socket-allreduce base port (no-op on TPU)
      deviceType: Compute placement: tpu|cpu|gpu
      driverListenPort: Legacy driver rendezvous port (no-op on TPU)
      earlyStoppingRound: Early stopping patience (0 = off)
      featureFraction: Feature subsample fraction
      featuresCol: The name of the features column
      growPolicy: lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
      histMerge: Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
      histQuantize: Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
      initScoreCol: Initial (margin) score column
      isProvideTrainingMetric: Record metrics on training data too
      isUnbalance: Reweight unbalanced binary labels
      labelCol: The name of the label column
      lambdaL1: L1 regularization
      lambdaL2: L2 regularization
      leafPredictionCol: Output column of leaf indices
      learningRate: Shrinkage rate
      matrixType: auto|dense|sparse host matrix handling
      maxBin: Max feature bins
      maxDepth: Max tree depth (-1 = unlimited)
      metric: Eval metric ('' = objective default)
      minDataInLeaf: Min rows per leaf
      minSumHessianInLeaf: Min leaf hessian sum
      modelString: Warm-start model string
      numBatches: Split training into sequential batches (continuation-trained)
      numGradQuantBins: LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
      numIterations: Number of boosting iterations
      numLeaves: Max leaves per tree
      numTasks: Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
      numThreads: Host-side threads for binning (0 = default)
      objective: Training objective
      parallelism: Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
      predictBackend: Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
      predictionCol: The name of the prediction column
      quantTrainRenewLeaf: LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
      seed: Master random seed
      slotNames: Feature vector slot names
      splitBatch: k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
      stochasticRounding: LightGBM's stochastic_rounding: False rounds gradients to the nearest level
      timeout: Distributed initialization timeout in seconds
      topK: Top-k features voted per worker in voting_parallel
      useBarrierExecutionMode: Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
      useQuantizedGrad: LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
      validationIndicatorCol: Boolean column marking validation rows
      verbosity: Native verbosity
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, baggingFraction=1.0, baggingFreq=0, baggingSeed=3, boostFromAverage=True, booster=_UNSET, boostingType='gbdt', categoricalSlotIndexes=None, categoricalSlotNames=None, defaultListenPort=12400, deviceType='tpu', driverListenPort=0, earlyStoppingRound=0, featureFraction=1.0, featuresCol='features', growPolicy='lossguide', histMerge='auto', histQuantize='off', initScoreCol=_UNSET, isProvideTrainingMetric=False, isUnbalance=False, labelCol='label', lambdaL1=0.0, lambdaL2=0.0, leafPredictionCol='', learningRate=0.1, matrixType='auto', maxBin=255, maxDepth=-1, metric='', minDataInLeaf=20, minSumHessianInLeaf=0.001, modelString='', numBatches=0, numGradQuantBins=0, numIterations=100, numLeaves=31, numTasks=0, numThreads=0, objective='regression', parallelism='data_parallel', predictBackend='auto', predictionCol='prediction', quantTrainRenewLeaf=True, seed=0, slotNames=None, splitBatch=0, stochasticRounding=True, timeout=1200.0, topK=20, useBarrierExecutionMode=False, useQuantizedGrad=False, validationIndicatorCol=_UNSET, verbosity=1, weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class LightGBMRegressor(_LightGBMRegressor):
    """Generated wrapper over :class:`mmlspark_tpu.models.lightgbm.LightGBMRegressor`.

    Params:
      alpha: Quantile/huber alpha
      baggingFraction: Row subsample fraction
      baggingFreq: Resample bag every k iterations (0 = off)
      baggingSeed: Bagging random seed
      boostFromAverage: Seed scores at the label average
      boostingType: gbdt|rf|dart|goss (goss: each tree grown from an exact-count sample, the 20% of rows of largest gradient and 10% of the others at weight 8, LightGBM's top_rate/other_rate)
      categoricalSlotIndexes: Categorical feature indices
      categoricalSlotNames: Categorical feature names
      defaultListenPort: Legacy socket-allreduce base port (no-op on TPU)
      deviceType: Compute placement: tpu|cpu|gpu
      driverListenPort: Legacy driver rendezvous port (no-op on TPU)
      earlyStoppingRound: Early stopping patience (0 = off)
      featureFraction: Feature subsample fraction
      featuresCol: The name of the features column
      growPolicy: lossguide (leaf-wise; auto-batches splits on TPU — see splitBatch) | lossguide_exact (LightGBM's one-split-per-pass sequence, never batched) | depthwise (level-batched histograms, one pass per level)
      histMerge: Distributed histogram-merge strategy: auto (reduce_scatter when the mesh/feature shape profits — the benchmarked default, see BASELINE.md) | allreduce (every device receives the full merged histogram) | reduce_scatter (each device receives only its feature slice + a best-split allgather)
      histQuantize: Quantized training wire/accumulator: off (default — bitwise the f32 path) | on (resolved to int16) | int16 | int32.  Quantizes per-row grad/hess to integer buckets (numGradQuantBins levels; ±127 where that is not set) with seeded stochastic rounding, accumulates int32 histograms and merges shards over an integer collective wire (f32 winner refinement keeps AUC parity)
      initScoreCol: Initial (margin) score column
      isProvideTrainingMetric: Record metrics on training data too
      isUnbalance: Reweight unbalanced binary labels
      labelCol: The name of the label column
      lambdaL1: L1 regularization
      lambdaL2: L2 regularization
      leafPredictionCol: Output column of leaf indices
      learningRate: Shrinkage rate
      matrixType: auto|dense|sparse host matrix handling
      maxBin: Max feature bins
      maxDepth: Max tree depth (-1 = unlimited)
      metric: Eval metric ('' = objective default)
      minDataInLeaf: Min rows per leaf
      minSumHessianInLeaf: Min leaf hessian sum
      modelString: Warm-start model string
      numBatches: Split training into sequential batches (continuation-trained)
      numGradQuantBins: LightGBM's num_grad_quant_bins: gradients in [-bins/2, bins/2] and hessians in [0, bins] integer levels (LightGBM's default is 4); 0 = not given, the engine's 127 a side
      numIterations: Number of boosting iterations
      numLeaves: Max leaves per tree
      numTasks: Cap on parallel workers; 0 = one per DataFrame partition (reference: numWorkers = min(numTasks, partitions))
      numThreads: Host-side threads for binning (0 = default)
      objective: Training objective
      parallelism: Tree learner parallelism: data_parallel|voting_parallel|serial|feature_parallel
      predictBackend: Predict traversal backend: auto (pallas on TPU, packed elsewhere; re-resolved against the backend each predict runs on) | packed (depth-stepped device-resident node table) | pallas (fused VMEM row-tile kernel, TPU) | pallas_interpret (that kernel interpreted on CPU — tests/parity) | scan (legacy sequential per-tree lax.scan).  All backends score bitwise-identically.
      predictionCol: The name of the prediction column
      quantTrainRenewLeaf: LightGBM's quant_train_renew_leaf: leaf values from the rows' exact float32 gradient sums, which this engine always does; False is refused
      seed: Master random seed
      slotNames: Feature vector slot names
      splitBatch: k-batched best-first growth: apply up to k best splits per histogram pass (0 = auto: 8 on the TPU lossguide path — the benchmarked default, see BASELINE.md — policy default elsewhere; 1 = exact lossguide; -1 = never batch)
      stochasticRounding: LightGBM's stochastic_rounding: False rounds gradients to the nearest level
      timeout: Distributed initialization timeout in seconds
      topK: Top-k features voted per worker in voting_parallel
      tweedieVariancePower: Tweedie variance power (1..2)
      useBarrierExecutionMode: Gang-schedule training (the SPMD program launch is inherently gang-scheduled on TPU; kept for API parity)
      useQuantizedGrad: LightGBM's use_quantized_grad: quantized training on or off, the same switch as histQuantize (set one, or make them agree)
      validationIndicatorCol: Boolean column marking validation rows
      verbosity: Native verbosity
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, alpha=0.9, baggingFraction=1.0, baggingFreq=0, baggingSeed=3, boostFromAverage=True, boostingType='gbdt', categoricalSlotIndexes=None, categoricalSlotNames=None, defaultListenPort=12400, deviceType='tpu', driverListenPort=0, earlyStoppingRound=0, featureFraction=1.0, featuresCol='features', growPolicy='lossguide', histMerge='auto', histQuantize='off', initScoreCol=_UNSET, isProvideTrainingMetric=False, isUnbalance=False, labelCol='label', lambdaL1=0.0, lambdaL2=0.0, leafPredictionCol='', learningRate=0.1, matrixType='auto', maxBin=255, maxDepth=-1, metric='', minDataInLeaf=20, minSumHessianInLeaf=0.001, modelString='', numBatches=0, numGradQuantBins=0, numIterations=100, numLeaves=31, numTasks=0, numThreads=0, objective='regression', parallelism='data_parallel', predictBackend='auto', predictionCol='prediction', quantTrainRenewLeaf=True, seed=0, slotNames=None, splitBatch=0, stochasticRounding=True, timeout=1200.0, topK=20, tweedieVariancePower=1.5, useBarrierExecutionMode=False, useQuantizedGrad=False, validationIndicatorCol=_UNSET, verbosity=1, weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ONNXModel(_ONNXModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.onnx_model.ONNXModel`.

    Params:
      argMaxDict: Map input col -> output col to apply argmax to
      deviceType: Compute placement: tpu|cpu
      feedDict: Map of ONNX graph input name -> DataFrame column
      fetchDict: Map of output DataFrame column -> ONNX graph output name
      miniBatchSize: Rows per inference minibatch
      modelPayload: Serialized ONNX model bytes
      softMaxDict: Map input col -> output col to apply softmax to
    """

    def __init__(self, *, argMaxDict=None, deviceType='tpu', feedDict=None, fetchDict=None, miniBatchSize=64, modelPayload=_UNSET, softMaxDict=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RankingAdapter(_RankingAdapter):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RankingAdapter`.

    Params:
      k: Items to recommend
      labelCol: Output true-items column
      recommender: Inner recommender estimator
    """

    def __init__(self, *, k=10, labelCol='label', recommender=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RankingAdapterModel(_RankingAdapterModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RankingAdapterModel`.

    Params:
      k: Items to recommend
      labelCol: Output true-items column
      recommenderModel: Fitted recommender
    """

    def __init__(self, *, k=10, labelCol='label', recommenderModel=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RankingEvaluator(_RankingEvaluator):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RankingEvaluator`.

    Params:
      k: Cutoff
      labelCol: True item-list column
      metricName: ndcgAt|map|precisionAtk|recallAtK
      predictionCol: Predicted item-list column
    """

    def __init__(self, *, k=10, labelCol='label', metricName='ndcgAt', predictionCol='prediction'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RankingTrainValidationSplit(_RankingTrainValidationSplit):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RankingTrainValidationSplit`.

    Params:
      estimator: Recommender estimator
      itemCol: Item column
      k: Eval cutoff
      seed: Split seed
      trainRatio: Train fraction per user
      userCol: User column
    """

    def __init__(self, *, estimator=None, itemCol='item', k=10, seed=0, trainRatio=0.75, userCol='user'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RankingTrainValidationSplitModel(_RankingTrainValidationSplitModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RankingTrainValidationSplitModel`.

    Params:
      bestModel: Fitted recommender
      validationMetric: Holdout ranking metric
    """

    def __init__(self, *, bestModel=None, validationMetric=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RecommendationIndexer(_RecommendationIndexer):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RecommendationIndexer`.

    Params:
      itemInputCol: Raw item column
      itemOutputCol: Indexed item column
      ratingCol: Rating column
      userInputCol: Raw user column
      userOutputCol: Indexed user column
    """

    def __init__(self, *, itemInputCol='item', itemOutputCol='item_idx', ratingCol='rating', userInputCol='user', userOutputCol='user_idx'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RecommendationIndexerModel(_RecommendationIndexerModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.RecommendationIndexerModel`.

    Params:
      itemInputCol: Raw item column
      itemLevels: Item levels
      itemOutputCol: Indexed item column
      userInputCol: Raw user column
      userLevels: User levels
      userOutputCol: Indexed user column
    """

    def __init__(self, *, itemInputCol='item', itemLevels=None, itemOutputCol='item_idx', userInputCol='user', userLevels=None, userOutputCol='user_idx'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SAR(_SAR):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.SAR`.

    Params:
      activityTimeFormat: unused (API parity)
      itemCol: Item id column
      ratingCol: Rating column ('' = implicit 1.0)
      similarityFunction: cooccurrence|jaccard|lift
      supportThreshold: Min co-occurrence count
      timeCol: Event-time column (unix seconds)
      timeDecayCoeff: Affinity half-life in days
      userCol: User id column
    """

    def __init__(self, *, activityTimeFormat='', itemCol='item', ratingCol='rating', similarityFunction='jaccard', supportThreshold=4, timeCol='', timeDecayCoeff=30, userCol='user'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SARModel(_SARModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.sar.SARModel`.

    Params:
      activityTimeFormat: unused (API parity)
      itemCol: Item id column
      itemLevels: Item id order
      itemSimilarity: (I, I) similarity
      ratingCol: Rating column ('' = implicit 1.0)
      similarityFunction: cooccurrence|jaccard|lift
      supportThreshold: Min co-occurrence count
      timeCol: Event-time column (unix seconds)
      timeDecayCoeff: Affinity half-life in days
      userAffinity: (U, I) affinity matrix
      userCol: User id column
      userLevels: User id order
    """

    def __init__(self, *, activityTimeFormat='', itemCol='item', itemLevels=None, itemSimilarity=None, ratingCol='rating', similarityFunction='jaccard', supportThreshold=4, timeCol='', timeDecayCoeff=30, userAffinity=None, userCol='user', userLevels=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VowpalWabbitClassificationModel(_VowpalWabbitClassificationModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.vw.VowpalWabbitClassificationModel`.

    Params:
      batchSize: Minibatch size per SGD step
      featuresCol: The name of the features column
      hashSeed: Hash seed
      l1: L1 regularization
      l2: L2 regularization
      labelCol: The name of the label column
      learningRate: SGD learning rate
      lossFunction: logistic|squared
      numBits: log2 weight-space size
      numPasses: Passes over the data
      passThroughArgs: Raw VW argument string
      powerT: LR decay exponent t^-p
      predictionCol: The name of the prediction column
      probabilityCol: Probability column
      rawPredictionCol: Margin column
      weightCol: The name of the sample-weight column
      weights: Learned weight vector
    """

    def __init__(self, *, batchSize=256, featuresCol='features', hashSeed=0, l1=0.0, l2=0.0, labelCol='label', learningRate=0.5, lossFunction='logistic', numBits=18, numPasses=1, passThroughArgs='', powerT=0.5, predictionCol='prediction', probabilityCol='probability', rawPredictionCol='rawPrediction', weightCol=_UNSET, weights=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VowpalWabbitClassifier(_VowpalWabbitClassifier):
    """Generated wrapper over :class:`mmlspark_tpu.models.vw.VowpalWabbitClassifier`.

    Params:
      batchSize: Minibatch size per SGD step
      featuresCol: The name of the features column
      hashSeed: Hash seed
      l1: L1 regularization
      l2: L2 regularization
      labelCol: The name of the label column
      learningRate: SGD learning rate
      lossFunction: logistic|squared
      numBits: log2 weight-space size
      numPasses: Passes over the data
      passThroughArgs: Raw VW argument string
      powerT: LR decay exponent t^-p
      predictionCol: The name of the prediction column
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, batchSize=256, featuresCol='features', hashSeed=0, l1=0.0, l2=0.0, labelCol='label', learningRate=0.5, lossFunction='logistic', numBits=18, numPasses=1, passThroughArgs='', powerT=0.5, predictionCol='prediction', weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VowpalWabbitFeaturizer(_VowpalWabbitFeaturizer):
    """Generated wrapper over :class:`mmlspark_tpu.models.vw.VowpalWabbitFeaturizer`.

    Params:
      inputCols: Columns to hash
      numBits: log2 of the hashed space
      outputCol: Hashed vector column
      seed: Hash seed
      stringSplit: Split strings into words
      sumCollisions: Sum colliding features
    """

    def __init__(self, *, inputCols=None, numBits=18, outputCol='features', seed=0, stringSplit=False, sumCollisions=True):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VowpalWabbitInteractions(_VowpalWabbitInteractions):
    """Generated wrapper over :class:`mmlspark_tpu.models.vw.VowpalWabbitInteractions`.

    Params:
      inputCols: Vector columns to interact
      numBits: log2 of the hashed space
      outputCol: Interaction vector column
    """

    def __init__(self, *, inputCols=None, numBits=18, outputCol='features'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VowpalWabbitRegressionModel(_VowpalWabbitRegressionModel):
    """Generated wrapper over :class:`mmlspark_tpu.models.vw.VowpalWabbitRegressionModel`.

    Params:
      batchSize: Minibatch size per SGD step
      featuresCol: The name of the features column
      hashSeed: Hash seed
      l1: L1 regularization
      l2: L2 regularization
      labelCol: The name of the label column
      learningRate: SGD learning rate
      lossFunction: logistic|squared
      numBits: log2 weight-space size
      numPasses: Passes over the data
      passThroughArgs: Raw VW argument string
      powerT: LR decay exponent t^-p
      predictionCol: The name of the prediction column
      weightCol: The name of the sample-weight column
      weights: Learned weight vector
    """

    def __init__(self, *, batchSize=256, featuresCol='features', hashSeed=0, l1=0.0, l2=0.0, labelCol='label', learningRate=0.5, lossFunction='logistic', numBits=18, numPasses=1, passThroughArgs='', powerT=0.5, predictionCol='prediction', weightCol=_UNSET, weights=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class VowpalWabbitRegressor(_VowpalWabbitRegressor):
    """Generated wrapper over :class:`mmlspark_tpu.models.vw.VowpalWabbitRegressor`.

    Params:
      batchSize: Minibatch size per SGD step
      featuresCol: The name of the features column
      hashSeed: Hash seed
      l1: L1 regularization
      l2: L2 regularization
      labelCol: The name of the label column
      learningRate: SGD learning rate
      lossFunction: logistic|squared
      numBits: log2 weight-space size
      numPasses: Passes over the data
      passThroughArgs: Raw VW argument string
      powerT: LR decay exponent t^-p
      predictionCol: The name of the prediction column
      weightCol: The name of the sample-weight column
    """

    def __init__(self, *, batchSize=256, featuresCol='features', hashSeed=0, l1=0.0, l2=0.0, labelCol='label', learningRate=0.5, lossFunction='squared', numBits=18, numPasses=1, passThroughArgs='', powerT=0.5, predictionCol='prediction', weightCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ImageSetAugmenter(_ImageSetAugmenter):
    """Generated wrapper over :class:`mmlspark_tpu.ops.image_ops.ImageSetAugmenter`.

    Params:
      flipLeftRight: Add horizontal flips
      flipUpDown: Add vertical flips
      inputCol: Image column
      outputCol: Output image column
    """

    def __init__(self, *, flipLeftRight=True, flipUpDown=False, inputCol='image', outputCol='image'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ImageTransformer(_ImageTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.ops.image_ops.ImageTransformer`.

    Params:
      inputCol: Image struct column
      outputCol: Output image column
      stages: Ordered op list
    """

    def __init__(self, *, inputCol='image', outputCol='out_image', stages=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class UnrollBinaryImage(_UnrollBinaryImage):
    """Generated wrapper over :class:`mmlspark_tpu.ops.image_ops.UnrollBinaryImage`.

    Params:
      inputCol: Binary image column
      outputCol: Unrolled vector column
    """

    def __init__(self, *, inputCol='image', outputCol='unrolled'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class UnrollImage(_UnrollImage):
    """Generated wrapper over :class:`mmlspark_tpu.ops.image_ops.UnrollImage`.

    Params:
      inputCol: Image struct column
      outputCol: Unrolled vector column
    """

    def __init__(self, *, inputCol='image', outputCol='unrolled'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Cacher(_Cacher):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.Cacher`.

    Params:
      disable: Pass-through when true
    """

    def __init__(self, *, disable=False):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ClassBalancer(_ClassBalancer):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.ClassBalancer`.

    Params:
      broadcastJoin: unused (API parity)
      inputCol: Label column
      outputCol: Weight column
    """

    def __init__(self, *, broadcastJoin=False, inputCol='label', outputCol='weight'):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ClassBalancerModel(_ClassBalancerModel):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.ClassBalancerModel`.

    Params:
      inputCol: Label column
      outputCol: Weight column
      weights: level -> weight map
    """

    def __init__(self, *, inputCol='label', outputCol='weight', weights=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DropColumns(_DropColumns):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.DropColumns`.

    Params:
      cols: Columns to drop
    """

    def __init__(self, *, cols=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class EnsembleByKey(_EnsembleByKey):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.EnsembleByKey`.

    Params:
      collapseGroup: One row per key
      cols: Columns to ensemble
      keys: Grouping key columns
      strategy: mean (only supported strategy)
      vectorDims: unused (API parity)
    """

    def __init__(self, *, collapseGroup=True, cols=None, keys=None, strategy='mean', vectorDims=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Explode(_Explode):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.Explode`.

    Params:
      inputCol: Column of sequences
      outputCol: Exploded column
    """

    def __init__(self, *, inputCol=_UNSET, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Lambda(_Lambda):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.Lambda`.

    Params:
      transformFunc: df -> df callable
    """

    def __init__(self, *, transformFunc=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class MultiColumnAdapter(_MultiColumnAdapter):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.MultiColumnAdapter`.

    Params:
      baseStage: Stage with inputCol/outputCol
      inputCols: Input columns
      outputCols: Output columns
    """

    def __init__(self, *, baseStage=None, inputCols=None, outputCols=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class PartitionConsolidator(_PartitionConsolidator):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.PartitionConsolidator`.

    Params:
      concurrency: Target partition count
      concurrentTimeout: unused (API parity)
    """

    def __init__(self, *, concurrency=1, concurrentTimeout=0.0):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class RenameColumn(_RenameColumn):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.RenameColumn`.

    Params:
      inputCol: Existing column name
      outputCol: New column name
    """

    def __init__(self, *, inputCol=_UNSET, outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Repartition(_Repartition):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.Repartition`.

    Params:
      disable: Pass-through when true
      n: Target number of partitions
    """

    def __init__(self, *, disable=False, n=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SelectColumns(_SelectColumns):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.SelectColumns`.

    Params:
      cols: Columns to keep
    """

    def __init__(self, *, cols=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class StratifiedRepartition(_StratifiedRepartition):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.StratifiedRepartition`.

    Params:
      labelCol: Label column
      mode: native|equal|mixed
      seed: Random seed
    """

    def __init__(self, *, labelCol='label', mode='native', seed=0):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class SummarizeData(_SummarizeData):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.SummarizeData`.

    Params:
      basic: Include basic stats
      counts: Include count stats
      errorThreshold: Quantile error (unused: exact)
      percentiles: Include percentiles
    """

    def __init__(self, *, basic=True, counts=True, errorThreshold=0.0, percentiles=True):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TextPreprocessor(_TextPreprocessor):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.TextPreprocessor`.

    Params:
      inputCol: Input text column
      map: substring -> replacement map
      normFunc: lowerCase|identity pre-normalization
      outputCol: Output text column
    """

    def __init__(self, *, inputCol=_UNSET, map=None, normFunc='lowerCase', outputCol=_UNSET):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class Timer(_Timer):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.Timer`.

    Params:
      disableMaterialization: Skip forcing evaluation
      logToScala: Print timing lines
      stage: The wrapped stage
    """

    def __init__(self, *, disableMaterialization=True, logToScala=True, stage=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class UDFTransformer(_UDFTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.stages.basic.UDFTransformer`.

    Params:
      inputCol: Input column
      inputCols: Input columns (multi-arg UDF)
      outputCol: Output column
      udf: The per-value function
    """

    def __init__(self, *, inputCol=_UNSET, inputCols=None, outputCol=_UNSET, udf=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class DynamicMiniBatchTransformer(_DynamicMiniBatchTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.stages.minibatch.DynamicMiniBatchTransformer`.

    Params:
      maxBatchSize: Upper bound on batch size
    """

    def __init__(self, *, maxBatchSize=2147483647):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class FixedMiniBatchTransformer(_FixedMiniBatchTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.stages.minibatch.FixedMiniBatchTransformer`.

    Params:
      batchSize: Rows per batch
      buffered: unused (API parity)
      maxBufferSize: unused (API parity)
    """

    def __init__(self, *, batchSize=10, buffered=False, maxBufferSize=2147483647):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class FlattenBatch(_FlattenBatch):
    """Generated wrapper over :class:`mmlspark_tpu.stages.minibatch.FlattenBatch`.

    Params:
    """

    def __init__(self):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TimeIntervalMiniBatchTransformer(_TimeIntervalMiniBatchTransformer):
    """Generated wrapper over :class:`mmlspark_tpu.stages.minibatch.TimeIntervalMiniBatchTransformer`.

    Params:
      maxBatchSize: Upper bound on batch size
      millisToWait: Window length in ms
    """

    def __init__(self, *, maxBatchSize=2147483647, millisToWait=1000):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ComputeModelStatistics(_ComputeModelStatistics):
    """Generated wrapper over :class:`mmlspark_tpu.train.compute_statistics.ComputeModelStatistics`.

    Params:
      evaluationMetric: classification|regression|all|<specific metric>
      labelCol: True label column
      scoredLabelsCol: Predicted label column
      scoresCol: Probability/score column (classification)
    """

    def __init__(self, *, evaluationMetric='all', labelCol='label', scoredLabelsCol='prediction', scoresCol=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class ComputePerInstanceStatistics(_ComputePerInstanceStatistics):
    """Generated wrapper over :class:`mmlspark_tpu.train.compute_statistics.ComputePerInstanceStatistics`.

    Params:
      evaluationMetric: classification|regression|all
      labelCol: True label column
      scoredLabelsCol: Predicted label column
      scoresCol: Probability column
    """

    def __init__(self, *, evaluationMetric='all', labelCol='label', scoredLabelsCol='prediction', scoresCol=None):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TrainClassifier(_TrainClassifier):
    """Generated wrapper over :class:`mmlspark_tpu.train.train_classifier.TrainClassifier`.

    Params:
      featuresCol: Assembled features column
      labelCol: Label column
      model: Inner estimator
      numFeatures: Hash buckets for text columns
    """

    def __init__(self, *, featuresCol='features', labelCol='label', model=None, numFeatures=262144):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TrainRegressor(_TrainRegressor):
    """Generated wrapper over :class:`mmlspark_tpu.train.train_classifier.TrainRegressor`.

    Params:
      featuresCol: Assembled features column
      labelCol: Label column
      model: Inner estimator
      numFeatures: Hash buckets for text columns
    """

    def __init__(self, *, featuresCol='features', labelCol='label', model=None, numFeatures=262144):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TrainedClassifierModel(_TrainedClassifierModel):
    """Generated wrapper over :class:`mmlspark_tpu.train.train_classifier.TrainedClassifierModel`.

    Params:
      featuresCol: Assembled features column
      featurizerModel: Fitted featurizer
      innerModel: Fitted inner model
      labelCol: Label column
      labelLevels: Original label levels
      model: Inner estimator
      numFeatures: Hash buckets for text columns
    """

    def __init__(self, *, featuresCol='features', featurizerModel=None, innerModel=None, labelCol='label', labelLevels=None, model=None, numFeatures=262144):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


class TrainedRegressorModel(_TrainedRegressorModel):
    """Generated wrapper over :class:`mmlspark_tpu.train.train_classifier.TrainedRegressorModel`.

    Params:
      featuresCol: Assembled features column
      featurizerModel: Fitted featurizer
      innerModel: Fitted inner model
      labelCol: Label column
      labelLevels: Original label levels
      model: Inner estimator
      numFeatures: Hash buckets for text columns
    """

    def __init__(self, *, featuresCol='features', featurizerModel=None, innerModel=None, labelCol='label', labelLevels=None, model=None, numFeatures=262144):
        kw = {k: v for k, v in locals().items()
              if k not in ('self', '__class__') and v is not _UNSET}
        super().__init__(**kw)


__all__ = [
    'BestModel',
    'FindBestModel',
    'TuneHyperparameters',
    'TuneHyperparametersModel',
    'BingImageSearch',
    'DetectEntireSeries',
    'DetectLastAnomaly',
    'FindSimilarFace',
    'GroupFaces',
    'IdentifyFaces',
    'VerifyFaces',
    'SpeechToText',
    'EntityDetector',
    'KeyPhraseExtractor',
    'LanguageDetector',
    'NER',
    'TextSentiment',
    'Translate',
    'AnalyzeImage',
    'DescribeImage',
    'DetectFace',
    'OCR',
    'TagImage',
    'Pipeline',
    'PipelineModel',
    'ImageLIME',
    'TabularLIME',
    'TabularLIMEModel',
    'SuperpixelTransformer',
    'CleanMissingData',
    'CleanMissingDataModel',
    'DataConversion',
    'Featurize',
    'FeaturizeModel',
    'IndexToValue',
    'ValueIndexer',
    'ValueIndexerModel',
    'TextFeaturizer',
    'TextFeaturizerModel',
    'HTTPTransformer',
    'JSONInputParser',
    'JSONOutputParser',
    'SimpleHTTPTransformer',
    'CNTKModel',
    'ImageFeaturizer',
    'IsolationForest',
    'IsolationForestModel',
    'ConditionalKNN',
    'ConditionalKNNModel',
    'KNN',
    'KNNModel',
    'LightGBMClassificationModel',
    'LightGBMClassifier',
    'LightGBMRanker',
    'LightGBMRankerModel',
    'LightGBMRegressionModel',
    'LightGBMRegressor',
    'ONNXModel',
    'RankingAdapter',
    'RankingAdapterModel',
    'RankingEvaluator',
    'RankingTrainValidationSplit',
    'RankingTrainValidationSplitModel',
    'RecommendationIndexer',
    'RecommendationIndexerModel',
    'SAR',
    'SARModel',
    'VowpalWabbitClassificationModel',
    'VowpalWabbitClassifier',
    'VowpalWabbitFeaturizer',
    'VowpalWabbitInteractions',
    'VowpalWabbitRegressionModel',
    'VowpalWabbitRegressor',
    'ImageSetAugmenter',
    'ImageTransformer',
    'UnrollBinaryImage',
    'UnrollImage',
    'Cacher',
    'ClassBalancer',
    'ClassBalancerModel',
    'DropColumns',
    'EnsembleByKey',
    'Explode',
    'Lambda',
    'MultiColumnAdapter',
    'PartitionConsolidator',
    'RenameColumn',
    'Repartition',
    'SelectColumns',
    'StratifiedRepartition',
    'SummarizeData',
    'TextPreprocessor',
    'Timer',
    'UDFTransformer',
    'DynamicMiniBatchTransformer',
    'FixedMiniBatchTransformer',
    'FlattenBatch',
    'TimeIntervalMiniBatchTransformer',
    'ComputeModelStatistics',
    'ComputePerInstanceStatistics',
    'TrainClassifier',
    'TrainRegressor',
    'TrainedClassifierModel',
    'TrainedRegressorModel',
]
